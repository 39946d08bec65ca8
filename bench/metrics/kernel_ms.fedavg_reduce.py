"""Device time of the ``fedavg_reduce`` Pallas kernel per round, ms: the
summed durations of its events in the traced window over the rounds the
window completed."""


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.window.rounds <= 0 or not tr.kernel_events("fedavg_reduce"):
        return None
    return 1e3 * tr.kernel_seconds("fedavg_reduce") / ctx.window.rounds
