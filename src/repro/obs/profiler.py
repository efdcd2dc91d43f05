"""Profiler scopes, host spans and wall-clock phase timing for the
training/serving stack.

Three layers, cheapest first:

* :func:`scope` — names a phase *inside* traced code (``jax.named_scope``):
  the gradient, DR-weighting, consensus and kernel phases of the train step
  carry ``obs:...`` scopes, so XLA traces and HLO dumps attribute ops to
  algorithm phases.  Trace-time only; the compiled program is unchanged.
* :func:`host_scope` — a host-side span: batch sampling, eval hooks, segment
  dispatch, the serving engine's admissions and decode steps.  Each span is
  recorded twice: as a ``jax.profiler.TraceAnnotation`` on the profiler
  timeline (its attributes encoded only while a trace is active), and in a
  bounded process-wide ring that the process itself reads back
  (:func:`spans`).  Spans nest through a per-thread stack; attributes carry
  the counters measured at that boundary.
* :class:`PhaseTimer` — wall-clock accounting per phase, rolled up per
  ``run_segments`` chunk into ``perf`` telemetry records (steps/s, wire
  bytes/s) by :func:`repro.core.api.run_segments`.

Spans are timed with ``time.perf_counter_ns()``.  One ``(time.time_ns(),
perf_counter_ns())`` anchor per process (:func:`span_wall_ns`) maps them onto
wall time, and so onto a profile's clock: the profiler's host and device
times are nanoseconds since the profile's ``profile_start_time`` (a stat of
the ``.xplane.pb``'s ``Task Environment`` plane), a ``time_ns`` value.

The :func:`profile` context manager wraps a region in ``jax.profiler.trace``
and returns the perfetto trace file XLA dumped (open it at
https://ui.perfetto.dev or ``tensorboard --logdir``; see EXPERIMENTS.md
§Observability).
"""

from __future__ import annotations

import collections
import contextlib
import glob
import itertools
import os
import threading
import time
from typing import NamedTuple

import jax

#: spans the process-wide ring holds; a 60-s serve run records about 8k
SPAN_CAPACITY = 1 << 17

#: one (time.time_ns(), time.perf_counter_ns()) pair per process
_ANCHOR = (time.time_ns(), time.perf_counter_ns())


def scope(name: str):
    """Phase scope for *traced* code: names the ops in HLO/profiler traces.

    Pure metadata — adding or removing a scope never changes numerics or
    program structure, which is what lets the obs layer guarantee
    bit-exactness with telemetry on.
    """
    return jax.named_scope(name)


class Span(NamedTuple):
    """One finished host span; times are ``time.perf_counter_ns()``."""

    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: int | None
    attrs: dict

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


class SpanRing:
    """Bounded buffer of finished spans: when full, the oldest span goes and
    :attr:`dropped` counts it, so a reader can tell a truncated record.

    It keeps each span as a plain tuple of atoms, its attributes as a tuple
    of items: the garbage collector stops tracking such a tuple (a ``Span``,
    a tuple subclass, or a tuple holding a dict stays tracked), so a full
    ring adds nothing to a full collection's walk.  :meth:`snapshot` builds
    the ``Span`` records."""

    def __init__(self, capacity: int = SPAN_CAPACITY):
        self._buf: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._buf.maxlen

    def append(self, span: tuple) -> None:
        """Keep one span, given as a ``Span`` or a tuple of its fields."""
        kept = (*span[:5], tuple(span[5].items()))
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(kept)

    def snapshot(self) -> list[Span]:
        with self._lock:
            kept = list(self._buf)
        return [Span(*t[:5], dict(t[5])) for t in kept]

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0


#: the process-wide ring every host_scope appends to: a reader in the same
#: process (a benchmark after its window) reads the spans of code it does
#: not hold a handle to
_RING = SpanRing()
_IDS = itertools.count(1)
_LOCAL = threading.local()


def spans() -> list[Span]:
    """A snapshot of the finished spans the ring holds, oldest first."""
    return _RING.snapshot()


def clear_spans() -> None:
    """Empty the ring and reset its drop count."""
    _RING.clear()


def dropped_spans() -> int:
    """Spans the ring has dropped since it was last cleared."""
    return _RING.dropped


def span_wall_ns(perf_ns: int) -> int:
    """A span time (``perf_counter_ns``) as ``time.time_ns()`` would read."""
    return _ANCHOR[0] + (perf_ns - _ANCHOR[1])


class HostScope:
    """Context manager of one host span (see :func:`host_scope`); after the
    block, ``start_ns``/``end_ns`` hold its times and :attr:`seconds` its
    duration."""

    __slots__ = ("name", "step", "attrs", "span_id", "parent_id",
                 "start_ns", "end_ns", "_ann")

    def __init__(self, name: str, step: int | None, attrs: dict):
        self.name, self.step, self.attrs = name, step, attrs
        self._ann = None

    def __enter__(self) -> HostScope:
        stack = getattr(_LOCAL, "stack", None)
        if stack is None:
            stack = _LOCAL.stack = []
        self.parent_id = stack[-1] if stack else None
        self.span_id = next(_IDS)
        stack.append(self.span_id)
        # an annotation records nothing unless a trace is active, so build
        # one (and encode the attributes into it) only then
        if jax.profiler.TraceAnnotation.is_enabled():
            if self.step is None:
                self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
            else:
                self._ann = jax.profiler.StepTraceAnnotation(
                    self.name, step_num=self.step, **self.attrs)
            self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _LOCAL.stack.pop()
        attrs = self.attrs if self.step is None else {"step": self.step,
                                                      **self.attrs}
        _RING.append((self.name, self.start_ns, self.end_ns, self.span_id,
                      self.parent_id, attrs))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def host_scope(name: str, *, step: int | None = None, **attrs) -> HostScope:
    """A span of host-side code: on the profiler timeline (a
    ``jax.profiler.TraceAnnotation``, or a ``StepTraceAnnotation`` with
    ``step_num=step`` when ``step`` is given) and in the in-memory ring
    (:func:`spans`).  ``attrs`` are the span's counters; the parent is the
    innermost span open on this thread."""
    return HostScope(name, step, attrs)


class PhaseTimer:
    """Wall-clock seconds per named phase; one rollup per logging chunk.

    Usage::

        timer = PhaseTimer()
        with timer.phase("sample"): batches = ...
        with timer.phase("run"):    state, ms = trainer.run(state, batches)
        rec = timer.rollup(steps=n, wire_bytes=float(ms["comm_bytes"].sum()))
        timer.reset()

    Each ``phase`` block is a :func:`host_scope` named ``obs:<phase>``, and
    the rollup sums those spans' durations, so a ``--profile`` trace and the
    span ring show the same phases the rollup reports.
    """

    def __init__(self):
        self.phases: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        span = host_scope(f"obs:{name}")
        try:
            with span:
                yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + span.seconds

    def reset(self) -> None:
        self.phases = {}

    def rollup(self, *, steps: int = 0, wire_bytes: float | None = None,
               run_phase: str = "run") -> dict:
        """The chunk's ``perf`` record fields (see repro.obs.schema).

        ``steps_per_s`` divides by the ``run_phase`` time when present (the
        compiled-scan wall time), else by the total; ``wall_s`` is always the
        total across phases.
        """
        wall = sum(self.phases.values())
        run_s = self.phases.get(run_phase, wall)
        rec = {
            "wall_s": wall,
            "steps": steps,
            "steps_per_s": (steps / run_s) if steps and run_s > 0 else 0.0,
            "phase_s": {k: round(v, 6) for k, v in self.phases.items()},
        }
        if wire_bytes is not None and run_s > 0:
            rec["wire_bytes_per_s"] = wire_bytes / run_s
        return rec


def find_perfetto_trace(log_dir: str) -> str | None:
    """The perfetto trace file a ``jax.profiler.trace(log_dir)`` run dumped."""
    pats = [
        os.path.join(log_dir, "plugins", "profile", "*", "*.trace.json.gz"),
        os.path.join(log_dir, "plugins", "profile", "*", "*.trace.json"),
    ]
    hits = sorted(h for p in pats for h in glob.glob(p))
    return hits[-1] if hits else None


@contextlib.contextmanager
def profile(log_dir: str | None, enabled: bool = True):
    """Wrap a region in ``jax.profiler.trace`` and yield a result holder.

    ``enabled=False`` (or ``log_dir=None``) is a no-op, so call sites can
    thread a ``--profile`` flag straight through.  On exit the holder's
    ``trace_path`` points at the perfetto trace (or None if the backend
    produced none).
    """
    holder = type("ProfileResult", (), {"trace_path": None})()
    if not enabled or log_dir is None:
        yield holder
        return
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield holder
    holder.trace_path = find_perfetto_trace(log_dir)
