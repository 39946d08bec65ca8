"""Local forward time per round, ms: the device ops of the local loss,
under the program's ``fl.local.loss`` scope (``jvp(fl.local.loss)`` where
the loss is differentiated) and not under its transpose, over the rounds
the window completed."""
from bench import xspace


def read(ctx):
    return xspace.per_round_ms(
        ctx, lambda scope: "fl.local.loss" in scope and "transpose(" not in scope)
