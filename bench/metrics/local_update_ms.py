"""Local optimizer time per round, ms: the device ops under the program's
``fl.local.update`` scope (the optimizer step, the trainable-mask select
and the step-budget select), over the rounds the window completed."""
from bench import xspace


def read(ctx):
    return xspace.per_round_ms(ctx, lambda scope: scope == "fl.local.update")
