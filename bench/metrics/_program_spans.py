"""Shared by the readers of the serving engine's own host spans and counters
(``repro.obs.spans()``, in this process, after the window): whether the cell
being read is one the metric lists, and the spans of the window's
``ServeEngine.run``.

Each of these metrics lists its cells (``workloads`` in ``BENCHMARK.json``)
and reads nothing elsewhere (:func:`listed`).  Only a program that records
no spans at all (one older than the span ring) gives None, and the metric is
left out of the line.  A program that has the ring but lacks a run, a span
or a counter a reader needs, or a run the ring lost spans of, stops the run.
"""

from __future__ import annotations

import os

from bench.harness import common


def listed(ctx, metric: str) -> bool:
    """Whether the cell being read, the one whose configuration and traffic
    are ``ctx.cfg`` and ``ctx.job``, is among the cells ``metric`` lists."""
    bj = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    entry = next(m for m in bj["per_layer"] if m["name"] == metric)
    cells = {w["name"]: w for w in bj["workloads"]}
    files = {c["name"]: c["file"] for c in bj["configs"]}
    for name in entry["workloads"]:
        cell = cells[name]
        cfg = common.load_json(os.path.join(common.ROOT, files[cell["config"]]))
        job = common.load_json(os.path.join(common.BENCH, "traffic",
                                            cell["traffic"] + ".json"))
        if cfg == ctx.cfg and job == ctx.job:
            return True
    return False


def has_spans() -> bool:
    """Whether the program records host spans (``repro.obs.spans``)."""
    try:
        from repro.obs import spans  # noqa: F401
    except ImportError:
        return False
    return True


def run_tree():
    """The spans under the last ``obs:serve/run`` (the window's run)."""
    from repro.obs import dropped_spans, spans

    snap = spans()
    runs = [s for s in snap if s.name == "obs:serve/run"]
    if not runs:
        raise RuntimeError("the program's span ring holds no obs:serve/run")
    run = runs[-1]
    # the ring drops its oldest spans first: none of this run's went if the
    # oldest kept span ended before the run began
    if dropped_spans() and snap[0].end_ns >= run.start_ns:
        raise RuntimeError(f"the span ring dropped {dropped_spans()} spans, "
                           "some of them the window's run")
    parent = {s.span_id: s.parent_id for s in snap}
    member = {run.span_id: True}

    def belongs(sid):
        chain = []
        while sid is not None and sid not in member:
            chain.append(sid)
            sid = parent.get(sid)
        ok = sid is not None and member[sid]
        for c in chain:
            member[c] = ok
        return ok

    return [s for s in snap if s is not run and belongs(s.span_id)]


def named(tree, name: str) -> list:
    """The spans of ``tree`` named ``name``; there must be some."""
    got = [s for s in tree if s.name == name]
    if not got:
        raise RuntimeError(f"the window's run holds no {name} span")
    return got


def self_ms(tree, name: str, child: str, *, has_child=lambda span: True):
    """Mean, over the spans named ``name``, of each one's duration less
    that of its children named ``child``, in ms.  Every span for which
    ``has_child`` holds must have such a child."""
    kids: dict[int, int] = {}
    for s in tree:
        if s.name == child:
            kids[s.parent_id] = kids.get(s.parent_id, 0) + s.dur_ns
    spans = named(tree, name)
    lost = [s.span_id for s in spans if has_child(s) and s.span_id not in kids]
    if lost:
        raise RuntimeError(f"{len(lost)} {name} spans have no {child} child")
    return sum(s.dur_ns - kids.get(s.span_id, 0) for s in spans) / len(spans) / 1e6
