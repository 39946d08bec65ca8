"""Uplink encode time per round, ms: the device ops under the program's
``fl.encode`` scope (error feedback in, the codec's encode, the residual
out), over the rounds the window completed."""
from bench import xspace


def read(ctx):
    return xspace.per_round_ms(ctx, lambda scope: scope == "fl.encode")
