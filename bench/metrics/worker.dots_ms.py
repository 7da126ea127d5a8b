"""worker.dots_ms: device time per call of the ops scoped coded.dots."""

from bench import stages


def read(ctx):
    """Self time per call of the traced calls' worker products proper (the
    slice-pair dots, their scaled sums, the loop over workers), on chip 0,
    or on the first chip whose record names the program's ops
    (bench.stages); nothing without the scopes."""
    return stages.stage_ms(ctx, "coded.dots")
