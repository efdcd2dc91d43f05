"""Host ms per admission outside the wait for its prefill: the mean, over
the engine's ``obs:serve/admit`` spans in the window's run, of each one's
duration less that of its ``obs:serve/admit_wait`` child (table and slot
writes, the prefill's call, bookkeeping; a one-token prompt has no prefill
to wait for).  Read in the cells it lists."""

from bench.metrics._program_spans import has_spans, listed, run_tree, self_ms


def read(ctx):
    if not listed(ctx, "admit_host_ms.ttft") or not has_spans():
        return None
    return self_ms(run_tree(), "obs:serve/admit", "obs:serve/admit_wait",
                   has_child=lambda s: s.attrs["prompt_tokens"] > 0)
