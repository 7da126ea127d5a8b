"""worker_roofline: the plain product's least time over worker.stage_ms, in %.

The least time is the larger of 2 v r t operations at the int8 peak and
the plain operands' bytes (A and B int8, C int32) at peak HBM bandwidth
(``bench.peaks``): the work of the product, whatever computes it.
"""

from bench.peaks import least_time_s, plain_matmul_work


def read(ctx):
    """Nothing without a worker-stage time or the chip's peaks."""
    if not ctx.stages or "worker" not in ctx.stages or ctx.peaks is None:
        return None
    least, _ = least_time_s(*plain_matmul_work(*ctx.shape), ctx.peaks)
    return 100.0 * least / (ctx.stages["worker"] / 1e9)
