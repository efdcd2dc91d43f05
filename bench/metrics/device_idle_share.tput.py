"""Share of the traced window in which no operation runs on the device:
1 - (union of the device's op intervals) / window, averaged over chips."""

import numpy as np

from bench.metrics.trace import busy_ns


def read(ctx):
    win = ctx.window[1] - ctx.window[0]
    busy = float(np.mean([busy_ns(ctx.trace, d, ctx.window) for d in ctx.devices]))
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / win)
