"""Model FLOP utilization of the round, %: the FLOPs that the window's
trained samples require (forward and both gradients of what trains, and
the forward of any frozen part, from the configuration's
``flops_per_sample``; no recomputation, no frozen-part gradient), over the
window's seconds times chips times the chip's bf16 peak."""


def read(ctx):
    win = ctx.window
    if win.samples <= 0 or win.seconds <= 0:
        return None
    flops = win.samples * ctx.cell.reference.flops_per_sample(ctx.cell.config)["train"]
    peak = ctx.cell.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (win.seconds * peak)
