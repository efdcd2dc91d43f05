"""Share of its roofline that the prefill's selective scan reaches: the
least time of the scans over their device time, over the admissions the
trace holds (see _ssm_scan.py).

Each admission's ``obs:serve/admit`` span carries ``scan_tokens``, its
prompt tokens times the recurrent layers that scan them; the family's
``scan_cost`` gives the operations and bytes of one request's scans, and
its least time is the larger of operations over the bf16 peak and bytes
over the HBM peak.  The admissions with a prompt run a prefill program
each, in order, so the trace holds the first ones.  Read in the cells it
lists."""

from bench import families
from bench.metrics._program_spans import has_spans, listed, named, run_tree
from bench.metrics._ssm_scan import covered_scan


def read(ctx):
    if not listed(ctx, "ssm_scan_roofline.tput") or not has_spans():
        return None
    measured, admitted = covered_scan(ctx)
    if measured <= 0 or not admitted:
        return None
    fam = families.load(ctx.cfg)
    layers = fam.mamba_layers(ctx.cfg)
    admits = sorted((s for s in named(run_tree(), "obs:serve/admit")
                     if s.attrs.get("prompt_tokens")), key=lambda s: s.start_ns)
    least = 0.0
    for admit in admits[:admitted]:
        scanned = admit.attrs.get("scan_tokens", 0)
        if scanned:
            ops, nbytes = fam.scan_cost(ctx.cfg, scanned // layers)
            least += max(ops / ctx.peaks["bf16_flops"],
                         nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (measured / 1e9) if least > 0 else None
