"""Host ms per decode step outside its readback: the mean, over the
engine's ``obs:serve/step`` spans in the window's run, of each one's
duration less that of its ``obs:serve/readback`` child (the dispatch and
the per-slot work).  Read in the cells it lists."""

from bench.metrics._program_spans import has_spans, listed, run_tree, self_ms


def read(ctx):
    if not listed(ctx, "decode_host_ms.tpot") or not has_spans():
        return None
    return self_ms(run_tree(), "obs:serve/step", "obs:serve/readback")
