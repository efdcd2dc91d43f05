"""As admit_host_ms.ttft, read in the cells that report
``serve_output_tokens_per_s``."""

from bench.metrics._program_spans import has_spans, reports, run_tree, self_ms


def read(ctx):
    if not reports(ctx, "serve_output_tokens_per_s") or not has_spans():
        return None
    return self_ms(run_tree(), "obs:serve/admit", "obs:serve/admit_wait",
                   has_child=lambda s: s.attrs["prompt_tokens"] > 0)
