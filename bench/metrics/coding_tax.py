"""coding_tax: call_ms over the plain exact A^T B of the same operands."""


def read(ctx):
    """Both on the host clock in the same run; the plain product on one chip."""
    return ctx.window_s / len(ctx.latencies_s) / ctx.plain_s
