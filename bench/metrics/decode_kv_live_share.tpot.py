"""Percent of what the decode steps read of the KV pools that is live
context: 100 x the sum of ``kv_live_tokens`` over the sum of
``kv_gathered_tokens``, the counters of the engine's ``obs:serve/step``
spans in the window's run.  Read in the cells it lists."""

from bench.metrics._program_spans import has_spans, listed, named, run_tree


def read(ctx):
    if not listed(ctx, "decode_kv_live_share.tpot") or not has_spans():
        return None
    steps = [s.attrs for s in named(run_tree(), "obs:serve/step")]
    gathered = sum(a["kv_gathered_tokens"] for a in steps)
    if not gathered:
        raise RuntimeError("the decode steps gathered no KV tokens")
    return 100.0 * sum(a["kv_live_tokens"] for a in steps) / gathered
