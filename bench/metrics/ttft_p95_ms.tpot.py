"""95th percentile of the time to first token below the knee, as the
end-to-end ttft_p95_ms was: from each request's scheduled arrival to its
first token, a failed request counting as missing.  A p95 over the long
mix's 122 requests is their 7th-largest, which a host stall moves by a
third; recorded here, never judged.  It moves tpot_p95_ms: the admissions
that hold a first token back stall every running decode."""

from bench.harness.common import percentile


def read(ctx):
    ttft = ctx.host.get("ttft_ms")
    if ctx.kind != "serve" or not ttft:
        return None
    return percentile(ttft, 95)
