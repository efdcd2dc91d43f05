"""Device ms per admitted request of the Mamba layers' selective scan in
the prefill, over the admissions the trace holds (see _ssm_scan.py).  Read
in the cells it lists; a program without the scope there gives nothing,
and the run stops."""

from bench.metrics._program_spans import listed
from bench.metrics._ssm_scan import covered_scan


def read(ctx):
    if not listed(ctx, "ssm_scan_ms.tput"):
        return None
    ns, admitted = covered_scan(ctx)
    return ns / 1e6 / admitted if ns > 0 and admitted else None
