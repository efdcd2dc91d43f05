"""Device ms per training step in which a collective (a collective-permute
of the gossip wire, an all-reduce, ...) runs on a chip and no other op
overlaps it, averaged over the chips.  Read where the step has
collectives; moves train_tokens_per_s."""

import numpy as np

from bench.metrics.trace import COLLECTIVE_CATEGORIES, exposed_ns


def read(ctx):
    if ctx.kind != "train" or not ctx.counts.get("steps"):
        return None
    found = any(any(c in cat for c in COLLECTIVE_CATEGORIES)
                for d in ctx.devices
                for cat in ctx.trace.ops(d, ctx.window)["category"])
    if not found:
        return None
    ns = float(np.mean([exposed_ns(ctx.trace, d, ctx.window, COLLECTIVE_CATEGORIES)
                        for d in ctx.devices]))
    return ns / 1e6 / ctx.counts["steps"]
