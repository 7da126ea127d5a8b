"""device.idle_share: the window's share in which no op ran, in %."""

import statistics

from bench import tracefile


def read(ctx):
    """1 - busy / window per chip, from the trace; the mean over the chips."""
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * statistics.fmean(
        1.0 - tracefile.busy_ns(ctx.trace, d, lo, hi) / (hi - lo)
        for d in ctx.trace_devices)
