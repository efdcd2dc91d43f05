"""What every cell's run shares: finding the cell's files by name, the chip,
the compile cache, the traced window and the result line.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix.  Its files, found by those names:

* ``bench/configs/<config>.json``  -- the configuration, as it is run; its
  ``family`` names ``bench/families/<family>.py``, which maps it onto the
  program and gives its parameter layout, reference and costs;
* ``bench/traffic/<traffic>.json`` -- the traffic mix or training job; its
  ``kind`` (``train`` or ``serve``) picks the general runner;
* ``bench/limits/<workload>.json`` -- the limits of the numbers compared;
* ``bench/metrics/<metric>.py``    -- one reader per per-layer metric.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
#: the persistent compilation cache: a fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: where a traced run writes its profile (removed after it is read)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    """Everything one run of one cell needs, resolved from the files."""

    workload: str
    cfg: dict
    job: dict
    limits: dict
    end_to_end: list           # the cell's end-to-end metric entries
    per_layer: list            # the cell's per-layer metric entries
    chips: int
    seed: int
    seconds: float
    trace: bool
    peaks: dict | None = None  # the chip's row of peaks.json


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, *, seed: int, seconds: float, trace: bool) -> Spec:
    bj = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bj["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bj["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    job = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "limits", workload + ".json"))
    return Spec(
        workload=workload, cfg=cfg, job=job, limits=limits,
        end_to_end=[m for m in bj["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bj["per_layer"] if _applies(m, workload)],
        chips=int(cell["chips"]), seed=seed, seconds=seconds, trace=trace)


# -- the chip -------------------------------------------------------------------

def require_chip(spec: Spec):
    """The TPU devices of the run; exits non-zero, printing no result, when
    JAX finds no TPU, fewer chips than the cell asks for, or a chip that
    the peaks table does not know."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX found {devs[0].platform!r}); "
                 "the benchmark runs on a TPU only")
    if len(devs) < spec.chips:
        sys.exit(f"bench: {spec.workload} needs {spec.chips} chips, "
                 f"found {len(devs)}")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    kind = devs[0].device_kind
    if kind not in peaks:
        sys.exit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    spec.peaks = peaks[kind]
    return devs[:spec.chips]


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# -- the traced window ----------------------------------------------------------

@contextlib.contextmanager
def traced(enabled: bool):
    """Profile the block when ``enabled``; yields a holder whose ``trace``
    is the reduced :class:`bench.metrics.trace.Trace` after the block."""
    import jax

    holder = type("Traced", (), {"trace": None})()
    if not enabled:
        with jax.profiler.TraceAnnotation("bench:window"):
            yield holder
        return
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            yield holder
    finally:
        jax.profiler.stop_trace()
    from bench.metrics.trace import Trace, find_xplane

    holder.trace = Trace(find_xplane(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- per-layer metrics ----------------------------------------------------------

class Context:
    """What a per-layer metric reader gets."""

    def __init__(self, *, spec, trace, devices, window, window_s, counts,
                 host=None):
        self.kind = spec.job["kind"]
        self.cfg, self.job, self.peaks = spec.cfg, spec.job, spec.peaks
        self.chips = spec.chips
        self.trace, self.devices, self.window = trace, devices, window
        self.window_s, self.counts, self.host = window_s, counts, host or {}


def read_per_layer(spec: Spec, ctx) -> dict:
    """Run each per-layer metric's reader.  A reader that finds nothing to
    read returns None: a metric without ``workloads`` is then left out, but
    one that names this cell in its ``workloads`` must be read there, so the
    run stops (the program's step, scope or record it reads has gone)."""
    out = {}
    for m in spec.per_layer:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        elif spec.workload in m.get("workloads", ()):
            raise RuntimeError(
                f"per-layer metric {m['name']!r} found nothing to read in "
                f"{spec.workload}, a cell it lists")
    return out


def breakdown(ctx) -> dict:
    from bench.metrics.trace import idle_gaps, top_device_ops

    return {"device_ops": top_device_ops(ctx.trace, ctx.window),
            "idle_gaps": idle_gaps(ctx.trace, ctx.window)}


def busy_and_window(ctx) -> tuple[float, float]:
    from bench.metrics.trace import busy_ns

    busy = np.mean([busy_ns(ctx.trace, d, ctx.window) for d in ctx.devices])
    return float(busy) / 1e9, (ctx.window[1] - ctx.window[0]) / 1e9


# -- the result -----------------------------------------------------------------

def check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def emit(result: dict, checks: dict) -> None:
    """The checks as the last lines of stderr, then the result as the last
    line of stdout, with the checks under the key that comes last."""
    for name, c in checks.items():
        ok = c["value"] <= c["limit"]
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (as ``repro.obs.report._pctl``)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def now() -> float:
    return time.perf_counter()
