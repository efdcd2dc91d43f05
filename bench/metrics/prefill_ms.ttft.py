"""Device ms per admitted request under the ``obs:serve/prefill`` scope
(the model's prefill inside the engine's admission program)."""

from bench.metrics.trace import scope_ns


def read(ctx):
    if ctx.kind != "serve" or not ctx.counts.get("admitted"):
        return None
    ns = sum(scope_ns(ctx.trace, d, ctx.window, "obs:serve/prefill")
             for d in ctx.devices) / len(ctx.devices)
    return ns / 1e6 / ctx.counts["admitted"] if ns > 0 else None
