"""Peak device memory, GB: after the window,
``memory_stats()["peak_bytes_in_use"]``, the largest over the chips the
cell uses."""


def read(ctx):
    if ctx.memory_peak_bytes <= 0:
        return None
    return ctx.memory_peak_bytes / 1e9
