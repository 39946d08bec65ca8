"""Client batch assembly per fit, ms: the mean of the program's
``fl.fit.batch`` spans in the window (``JaxClient.fit``'s per-step
``next_batch`` gather and the ``np.stack`` of the round's batches)."""
from bench import xspace


def read(ctx):
    return xspace.mean_span_ms(ctx, "fl.fit.batch")
