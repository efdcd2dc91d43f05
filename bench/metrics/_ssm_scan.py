"""Shared by ssm_scan_*: the device time of the Mamba layers' selective
scan in the engine's prefill -- the ops whose name scope path holds both
``obs:serve/prefill`` and ``obs:ssm/scan`` (``models/ssm.py``) -- and the
admissions whose prefills that time belongs to.

The profiler stops recording when its buffer is full.  The scan runs some
eight device ops a token and Mamba layer, so at this cell's load a trace
holds only the window's first seconds (6.25 M ops and then none, on one
v5e).  Each reading therefore takes the admissions whose prefill program
(``jit_admit``) the trace holds, and the scan ops that end by the last of
them; where the trace holds the whole window, that is every admission."""

import numpy as np

from bench.metrics.trace import CONTAINERS

SCOPES = ("obs:serve/prefill", "obs:ssm/scan")
ADMIT = "jit_admit("


def covered_scan(ctx) -> tuple[float, int]:
    """(ns of scan ops, admissions) that the trace holds, averaged over the
    chips."""
    ns, admitted = [], []
    for d in ctx.devices:
        m = ctx.trace.modules(d, ctx.window)
        ends = [e for e, n in zip(m["end"], m["name"]) if n.startswith(ADMIT)]
        o = ctx.trace.ops(d, ctx.window)
        last = max(ends, default=-np.inf)
        ns.append(sum(
            t for t, e, op, c in zip(o["dur"], o["end"], o["tf_op"], o["category"])
            if e <= last and c not in CONTAINERS and all(s in op for s in SCOPES)))
        admitted.append(len(ends))
    return float(np.mean(ns)), int(round(np.mean(admitted)))
