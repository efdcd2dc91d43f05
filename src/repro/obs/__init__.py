"""``repro.obs`` — observability for the training/serving stack.

Three pieces, each usable alone:

* **Streaming telemetry** (:mod:`repro.obs.sink`): the train step packs
  its per-step record into payload leaves riding the scan's stacked
  outputs (zero host callbacks in the compiled program; a per-step
  ``io_callback`` variant remains for live streaming), drained into a
  host ring buffer and schema-versioned JSONL (:mod:`repro.obs.schema`),
  bit-exact and donation-preserving; console lines are formatters over
  the same records, so printed fields cannot drift from the persisted
  ones.
* **Profiler scopes and host spans** (:mod:`repro.obs.profiler`):
  ``obs:...`` named scopes on the gradient / DR-weighting / consensus /
  kernel phases and the serving engine's decode program; host spans
  (:func:`host_scope`) on the profiler timeline and in an in-memory ring
  the process reads back (:func:`spans`), with counters as attributes; a
  wall-clock :class:`PhaseTimer` rolled up per ``run_segments`` chunk; and
  a ``--profile`` perfetto-trace dump.
* **Recompile watchdog** (:mod:`repro.obs.watchdog`): jit-cache snapshots
  (:class:`RecompileWatchdog`) and a global compile counter
  (:func:`expect_compiles`) that turn the repo's zero-recompile invariant
  into a reusable guard for every benchmark, the launch driver, and the
  256-chip dryrun.
* **Event tracing** (:mod:`repro.obs.trace`): the ``trace`` record kind —
  serve request lifecycle spans and host-derived trainer round events
  (fault / EF re-base / rate switch), exportable to Chrome/perfetto
  trace-event JSON and mergeable onto a ``--profile`` timeline.
* **In-jit histograms** (:mod:`repro.obs.hist`): fixed-bin streaming
  counts over per-node loss / DR weights / EF innovation that ride the
  tap's decimated vector payload — no extra host callbacks.
* **Run report + regression gate** (:mod:`repro.obs.report`):
  ``python -m repro.obs report|compare`` folds a run's JSONL into the
  paper-facing fairness/comm/latency summary (text or HTML) and diffs two
  runs or BENCH files with CI-facing thresholds.
"""

from repro.obs.hist import TRAIN_HISTOGRAMS, HistSpec, hist_counts
from repro.obs.profiler import (
    PhaseTimer,
    Span,
    SpanRing,
    clear_spans,
    dropped_spans,
    find_perfetto_trace,
    host_scope,
    profile,
    scope,
    span_wall_ns,
    spans,
)
from repro.obs.report import (
    load_records,
    render_html,
    render_text,
    serve_latency_summary,
    summarize_run,
)
from repro.obs.schema import (
    SCHEMA_VERSION,
    validate_jsonl,
    validate_record,
)
from repro.obs.sink import (
    MetricsSink,
    format_eval,
    format_meta,
    format_perf,
    format_record,
    format_serve,
    format_trace,
    format_train,
)
from repro.obs.trace import (
    export_chrome_trace,
    merge_with_profile,
    to_chrome_events,
    trainer_trace_events,
)
from repro.obs.watchdog import (
    CompileCounter,
    RecompileError,
    RecompileWatchdog,
    expect_compiles,
    jit_cache_size,
)

__all__ = [
    "SCHEMA_VERSION", "validate_jsonl", "validate_record",
    "MetricsSink", "format_train", "format_eval", "format_perf",
    "format_meta", "format_record", "format_serve", "format_trace",
    "PhaseTimer", "scope", "host_scope", "profile", "find_perfetto_trace",
    "Span", "SpanRing", "spans", "clear_spans", "dropped_spans",
    "span_wall_ns",
    "RecompileWatchdog", "RecompileError", "CompileCounter",
    "expect_compiles", "jit_cache_size",
    "HistSpec", "hist_counts", "TRAIN_HISTOGRAMS",
    "trainer_trace_events", "to_chrome_events", "export_chrome_trace",
    "merge_with_profile",
    "load_records", "summarize_run", "serve_latency_summary",
    "render_text", "render_html",
]
