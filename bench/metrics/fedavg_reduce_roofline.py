"""Share of its roofline that ``fedavg_reduce`` reaches, %: the least time
its calls of a round could take (HBM-bound: each leaf's (C, n) client
params, the (n,) global and the (n,) output, at the chip's HBM bandwidth;
its 3 FLOPs an element are far below the compute bound), over the
kernel's measured device time per round."""
from bench import flops


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.window.rounds <= 0 or not tr.kernel_events("fedavg_reduce"):
        return None
    measured = tr.kernel_seconds("fedavg_reduce") / ctx.window.rounds
    least = sum(flops.least_seconds(flops.fedavg_reduce(ctx.runner.clients, n), ctx.peaks)
                for n in ctx.runner.leaf_sizes)
    return 100.0 * least / measured
