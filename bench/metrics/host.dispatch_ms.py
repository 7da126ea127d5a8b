"""host.dispatch_ms: mean host time from entering cm(...) to its return."""

import statistics


def read(ctx):
    """Harness clock around the facade call, before block_until_ready."""
    return 1e3 * statistics.fmean(ctx.dispatch_s)
