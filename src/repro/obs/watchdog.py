"""Recompile watchdog: jit-cache snapshots + a global compile counter.

The zero-recompile property is a load-bearing invariant of this repo: the
dynamics subsystem keeps topologies/faults/codec rates as *traced* operands
precisely so a whole sweep compiles one program.  Before this module, the
guard was a one-off ``run_programs == 1`` assertion in fig9; now every
benchmark (``benchmarks/common.run_decentralized``), the launch driver, and
the 256-chip dryrun get it uniformly:

* :class:`RecompileWatchdog` snapshots the jit cache size of tracked
  callables (``jax.jit``'s ``_cache_size()``) and raises
  :class:`RecompileError` (or warns) when a callable compiled more programs
  than its budget — e.g. a traced operand silently became a static one.

* :func:`expect_compiles` counts *process-global* backend compiles via
  ``jax.monitoring`` events around a region — the right tool when the code
  under guard compiles AOT (``lower().compile()``, as the dryrun does) and
  never populates a jit cache.

Both report, on violation, which callable grew and by how much, so the
failure message names the function to go stare at.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable

from jax import monitoring as _monitoring


class RecompileError(RuntimeError):
    """An observed compile/retrace count exceeded the declared budget."""


def jit_cache_size(fn) -> int:
    """Compiled-program count of a ``jax.jit`` callable (its cache size)."""
    cs = getattr(fn, "_cache_size", None)
    if cs is None:
        raise ValueError(
            f"{fn!r} has no _cache_size — pass the jax.jit-wrapped callable "
            "(e.g. trainer._run), not the python function")
    return int(cs())


class RecompileWatchdog:
    """Guard jitted callables against unexpected retraces.

    Usage::

        watch = RecompileWatchdog(label="fig9 dropout sweep")
        watch.track("run", trainer._run, allowed=1)
        ... drive the run ...
        watch.check()            # raises RecompileError on a retrace

    ``allowed`` is the compile budget per callable *from the moment it was
    tracked* (1 = the initial compile and nothing else).  ``check(extra=n)``
    tolerates n extra programs across the board — e.g. the ragged final
    segment of a chopped scan legitimately compiles one more scan length.

    ``on_violation="warn"`` logs instead of raising (the launch driver's
    default: a user run should finish, a benchmark should fail loudly).
    """

    def __init__(self, on_violation: str = "raise", label: str = ""):
        if on_violation not in ("raise", "warn"):
            raise ValueError(f"on_violation must be 'raise'|'warn', "
                             f"got {on_violation!r}")
        self.on_violation = on_violation
        self.label = label
        self._tracked: dict[str, dict[str, Any]] = {}
        self.violations: list[str] = []

    def track(self, name: str, fn: Callable, allowed: int = 1
              ) -> "RecompileWatchdog":
        """Start guarding ``fn`` (chainable). Baseline = its current cache."""
        self._tracked[name] = {
            "fn": fn, "baseline": jit_cache_size(fn), "allowed": allowed}
        return self

    def programs(self, name: str) -> int:
        """Programs compiled since ``track`` (0 = not yet executed)."""
        t = self._tracked[name]
        return jit_cache_size(t["fn"]) - t["baseline"]

    def snapshot(self) -> dict[str, int]:
        return {name: self.programs(name) for name in self._tracked}

    def check(self, extra_allowed: int = 0) -> dict[str, int]:
        """Verify every tracked callable stayed within budget.

        Returns the per-callable program counts; raises/warns on violation.
        """
        snap = self.snapshot()
        for name, programs in snap.items():
            budget = self._tracked[name]["allowed"] + extra_allowed
            if programs > budget:
                self._violate(
                    f"{name} compiled {programs} programs "
                    f"(budget {budget}) — an operand that must stay traced "
                    f"leaked into program structure")
        return snap

    def _violate(self, msg: str) -> None:
        full = f"recompile watchdog{f' [{self.label}]' if self.label else ''}: {msg}"
        self.violations.append(full)
        if self.on_violation == "raise":
            raise RecompileError(full)
        warnings.warn(full, RuntimeWarning, stacklevel=3)


class CompileCounter:
    """Process-global backend-compile counter (``jax.monitoring`` events).

    Counts every compile event the runtime reports while active — including
    AOT ``lower().compile()`` and the one-off compiles of tiny eager ops —
    so budgets should carry slack for first-touch eager constants.
    """

    _COMPILE_MARKERS = ("compile",)

    def __init__(self):
        self.count = 0
        self.events: list[str] = []

    def _listener(self, event: str, **_kw) -> None:
        if any(m in event for m in self._COMPILE_MARKERS):
            self.count += 1
            self.events.append(event)

    def __enter__(self) -> "CompileCounter":
        _monitoring.register_event_listener(self._listener)
        return self

    def __exit__(self, *exc) -> None:
        _monitoring.unregister_event_listener(self._listener)


class _ExpectCompiles:
    def __init__(self, at_most: int, label: str, on_violation: str):
        self.at_most = at_most
        self.watch = RecompileWatchdog(on_violation=on_violation, label=label)
        self.counter = CompileCounter()

    @property
    def count(self) -> int:
        return self.counter.count

    def __enter__(self) -> "_ExpectCompiles":
        self.counter.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.counter.__exit__(exc_type, exc, tb)
        if exc_type is None and self.counter.count > self.at_most:
            self.watch._violate(
                f"region performed {self.counter.count} backend compiles "
                f"(budget {self.at_most})")


def expect_compiles(at_most: int, *, label: str = "",
                    on_violation: str = "raise") -> _ExpectCompiles:
    """Context manager: fail if the region compiles more than ``at_most``.

    For AOT code paths with no jit cache to snapshot (the dryrun's
    ``lower().compile()`` probes)::

        with expect_compiles(at_most=8, label=tag):
            compile_and_measure(...)     # 1 compile
            fit_scan_correction(...)     # 2 probe compiles (+ eager noise)
    """
    return _ExpectCompiles(at_most, label, on_violation)
