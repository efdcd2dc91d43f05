"""Shared by mfu.decode_step.*: the decode steps' least time over their
measured device time.  A step's least time is the larger of its operations
over the peak bf16 rate and its needed bytes (parameters plus live keys and
values) over the peak HBM bandwidth; its device time is that of the
decode-step program (``jit_step``) in the trace."""

import numpy as np

from bench import families


def decode_roofline(ctx):
    steps = ctx.counts.get("decode_steps") or []
    if ctx.kind != "serve" or not steps:
        return None
    cost = families.load(ctx.cfg).decode_step_cost
    least = 0.0
    for active, kv in steps:
        flops, nbytes = cost(ctx.cfg, active, kv)
        least += max(flops / ctx.peaks["bf16_flops"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    dev = []
    for d in ctx.devices:
        m = ctx.trace.modules(d, ctx.window)
        dev.append(sum(t for t, n in zip(m["dur"], m["name"])
                       if n.startswith("jit_step(")))
    measured = float(np.mean(dev)) / 1e9
    if measured <= 0:
        return None
    return 100.0 * least / measured
