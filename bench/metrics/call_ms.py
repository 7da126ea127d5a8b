"""call_ms: the window's length over the exact coded calls completed in it."""


def read(ctx):
    """Host clock over the whole window, every call blocked until ready."""
    return 1e3 * ctx.window_s / len(ctx.latencies_s)
