"""Local backward time per round, ms: the device ops of the local loss's
gradient, under ``transpose(jvp(fl.local.loss))``, over the rounds the
window completed."""
from bench import xspace


def read(ctx):
    return xspace.per_round_ms(
        ctx, lambda scope: "fl.local.loss" in scope and "transpose(" in scope)
