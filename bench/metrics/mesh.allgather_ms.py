"""mesh.allgather_ms: device time per call of the all-gather ops."""

from bench import tracefile


def read(ctx):
    """Per chip, the traced window's time covered by ops named all-gather,
    over the calls in it; the chip where it is longest.  Nothing where none
    ran."""
    if ctx.trace is None or not ctx.trace_calls:
        return None
    per_chip = [tracefile.matching_ns(ctx.trace, d, "all-gather", *ctx.trace_window)
                for d in ctx.trace_devices]
    if not any(per_chip):
        return None
    return max(per_chip) / 1e6 / ctx.trace_calls
