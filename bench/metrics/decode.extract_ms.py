"""decode.extract_ms: device time per call of the ops scoped coded.extract."""

from bench import stages


def read(ctx):
    """Self time per call of the traced calls' digit extraction or rounding, on
    chip 0, or on the first chip whose record names the program's ops
    (bench.stages); nothing without the scopes."""
    return stages.stage_ms(ctx, "coded.extract")
