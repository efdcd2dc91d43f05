"""95th percentile of the per-request time per output token above the knee,
as tpot_p95_ms; recorded, never judged there."""

from bench.harness.common import percentile


def read(ctx):
    tpot = ctx.host.get("tpot_ms")
    if ctx.kind != "serve" or not tpot:
        return None
    return percentile(tpot, 95)
