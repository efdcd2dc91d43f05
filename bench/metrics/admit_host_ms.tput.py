"""As admit_host_ms.ttft, read in the cells it lists."""

from bench.metrics._program_spans import has_spans, listed, run_tree, self_ms


def read(ctx):
    if not listed(ctx, "admit_host_ms.tput") or not has_spans():
        return None
    return self_ms(run_tree(), "obs:serve/admit", "obs:serve/admit_wait",
                   has_child=lambda s: s.attrs["prompt_tokens"] > 0)
