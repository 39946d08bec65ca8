"""Client fit time, ms: the mean span of one ``JaxClient.fit`` (batch
assembly, host-to-device copy, the jitted local steps, the loss sync) over
the window's client updates."""


def read(ctx):
    fit = ctx.window.spans.get("fit")
    if not fit:
        return None
    return 1e3 * sum(fit) / len(fit)
