"""Device ms per decode step under the ``obs:serve/kv_gather`` scope: the
paged read of every slot's ring through the block tables, inside the decode
program (``models/attention.py``).  Steps are the harness's decode-step
records; read in the cells it lists.  A program older than the span ring
has no such scope either, and gives None; any other program must show the
scope."""

import numpy as np

from bench.metrics._program_spans import has_spans, listed
from bench.metrics.trace import scope_ns


def read(ctx):
    if not listed(ctx, "kv_gather_ms.tpot") or not has_spans():
        return None
    steps = len(ctx.counts.get("decode_steps") or ())
    ns = float(np.mean([scope_ns(ctx.trace, d, ctx.window, "obs:serve/kv_gather")
                        for d in ctx.devices]))
    if not steps or ns <= 0:
        raise RuntimeError(f"{steps} decode steps and {ns} ns under "
                           "obs:serve/kv_gather in the window")
    return ns / 1e6 / steps
