"""Client loss sync per fit, ms: the mean of the program's ``fl.fit.sync``
spans in the window, where ``JaxClient.fit`` waits on the device for its
mean loss."""
from bench import xspace


def read(ctx):
    return xspace.mean_span_ms(ctx, "fl.fit.sync")
