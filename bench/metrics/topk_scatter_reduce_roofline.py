"""Share of its roofline that ``topk_scatter_reduce`` reaches, %: the least
time its calls of a round could take (HBM-bound: each leaf's C x k int32
indices and fp32 values read once and its (n,) fp32 accumulator written
once, at the chip's HBM bandwidth), over the kernel's measured device time
per round.  k = max(1, floor(frac * n)) per leaf, as the codec keeps."""
import math

from bench import flops


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.window.rounds <= 0 or not tr.kernel_events("topk_scatter_reduce"):
        return None
    frac = ctx.cell.traffic["codec"]["frac"]
    measured = tr.kernel_seconds("topk_scatter_reduce") / ctx.window.rounds
    least = sum(flops.least_seconds(flops.topk_scatter_reduce(
        ctx.runner.clients, max(1, math.floor(n * frac)), n), ctx.peaks)
        for n in ctx.runner.leaf_sizes)
    return 100.0 * least / measured
