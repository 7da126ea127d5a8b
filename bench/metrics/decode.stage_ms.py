"""decode.stage_ms: device time of one call of the facade's decode_stage."""


def read(ctx):
    """The split-stage trace's jit_bench_decode_stage runs on the first chip;
    nothing where the backend has no split seam."""
    if not ctx.stages or "decode" not in ctx.stages:
        return None
    return ctx.stages["decode"] / 1e6
