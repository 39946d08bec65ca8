"""Device idle share, %: 1 - (busy union of the device's ops) / (traced
window), averaged over the chips the cell uses."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s() <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s())
