"""Device ms per training step under the ``obs:grad`` scope (per-node
forward and backward of the DR step), averaged over chips."""

import numpy as np

from bench.metrics.trace import scope_ns


def read(ctx):
    if ctx.kind != "train" or not ctx.counts.get("steps"):
        return None
    ns = float(np.mean([scope_ns(ctx.trace, d, ctx.window, "obs:grad")
                        for d in ctx.devices]))
    return ns / 1e6 / ctx.counts["steps"] if ns > 0 else None
