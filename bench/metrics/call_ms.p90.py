"""call_ms.p90: the 90th percentile of the window's call latencies."""

import numpy as np


def read(ctx):
    """Host clock per call, dispatch to ready; over every call in the window."""
    return 1e3 * float(np.percentile(ctx.latencies_s, 90))
