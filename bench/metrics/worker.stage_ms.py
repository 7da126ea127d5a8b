"""worker.stage_ms: device time of one call of the facade's worker_stage."""


def read(ctx):
    """The split-stage trace's jit_bench_worker_stage runs on the first chip;
    nothing where the backend has no split seam."""
    if not ctx.stages or "worker" not in ctx.stages:
        return None
    return ctx.stages["worker"] / 1e6
