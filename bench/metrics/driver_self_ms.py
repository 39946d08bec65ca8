"""Host driver time per round, ms: each round's span (``configure_fit`` to
the next round's) minus the client ``fit`` spans inside it, averaged over
the window's rounds."""


def read(ctx):
    self_s = ctx.window.spans.get("round_self")
    if not self_s:
        return None
    return 1e3 * sum(self_s) / len(self_s)
