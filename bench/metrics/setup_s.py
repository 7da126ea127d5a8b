"""setup_s: seconds from process start to the first timed call."""


def read(ctx):
    """Host clock: device init, operand pool, compile or cache load, warm-up."""
    return ctx.setup_s
