"""Server aggregation per round, ms: the mean of the program's
``fl.aggregate_fit`` spans in the window (the strategy's reduce of the
round's client results into the new global)."""
from bench import xspace


def read(ctx):
    return xspace.mean_span_ms(ctx, "fl.aggregate_fit")
