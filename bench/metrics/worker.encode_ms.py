"""worker.encode_ms: device time per call of the ops scoped coded.encode."""

from bench import stages


def read(ctx):
    """Self time per call of the traced calls' block decomposition and
    coefficient sums of A and B, on chip 0, or on the first chip whose
    record names the program's ops (bench.stages); nothing without the
    scopes."""
    return stages.stage_ms(ctx, "coded.encode")
