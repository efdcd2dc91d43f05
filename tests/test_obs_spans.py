"""Host spans (repro.obs.host_scope) and the serving engine's spans,
counters and decode-program scopes.

* The span ring: bounded, counts what it drops, nests spans through a
  per-thread stack, and carries counters as attributes.
* ``ServeEngine``: each decode step is one ``obs:serve/step`` span with
  ``dispatch``/``readback``/``emit`` children, whose counters equal the
  engine's slot state at dispatch; each admission's span names the request
  its lifecycle records name; the report's decode/prefill seconds are the
  spans' durations.
* The decode program: ``obs:serve/kv_gather`` and ``obs:serve/attend`` in
  its op metadata, and its jitted name stays ``step``.
* With the profiler on: the spans keep their tree and counters, and the
  process's one clock anchor maps them onto the profile's clock.
* The report CLI, in another process than the profiled run, merges the
  lifecycle records onto the profile's clock.
* The benchmark's span readers read the same numbers from a run, and stop
  a run whose spans are missing.
"""

import glob
import gzip
import importlib.util
import json
import os
import re
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_arch
from repro.models import TransformerLM
from repro.obs import profiler
from repro.serve import Request, ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the span ring ---------------------------------------------------------------

def _span(i):
    return obs.Span(f"s{i}", i, i + 1, i, None, {})


def test_span_ring_is_bounded_and_counts_drops():
    ring = obs.SpanRing(4)
    for i in range(6):
        ring.append(_span(i))
    kept = ring.snapshot()
    assert [s.name for s in kept] == ["s2", "s3", "s4", "s5"]
    assert ring.dropped == 2 and ring.capacity == 4
    ring.clear()
    assert ring.snapshot() == [] and ring.dropped == 0
    # the process-wide ring holds a whole serve window with room to spare
    assert profiler.SPAN_CAPACITY >= 1 << 17


def test_host_scope_nests_counts_and_drops(monkeypatch):
    monkeypatch.setattr(profiler, "_RING", obs.SpanRing(3))
    with obs.host_scope("obs:outer", requests=2) as outer:
        with obs.host_scope("obs:inner", step=7, active=3) as inner:
            pass
    got = obs.spans()
    assert [s.name for s in got] == ["obs:inner", "obs:outer"]
    by = {s.name: s for s in got}
    assert by["obs:outer"].parent_id is None
    assert by["obs:inner"].parent_id == by["obs:outer"].span_id
    assert by["obs:inner"].attrs == {"step": 7, "active": 3}
    assert by["obs:outer"].attrs == {"requests": 2}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert inner.seconds == pytest.approx(by["obs:inner"].dur_ns / 1e9)
    for _ in range(3):
        with obs.host_scope("obs:more"):
            pass
    assert len(obs.spans()) == 3 and obs.dropped_spans() == 2
    obs.clear_spans()
    assert obs.spans() == [] and obs.dropped_spans() == 0


# -- the serving engine's spans -------------------------------------------------

def _requests(vocab, n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, vocab, (s0,)).astype(np.int32),
                    max_new=k, arrival=float(a))
            for i, (s0, k, a) in enumerate(
                [(6, 4, 0), (10, 3, 0), (1, 3, 1), (6, 5, 2), (10, 2, 3),
                 (6, 3, 6)][:n])]


def _slot_state(engine, records, capped=False):
    """What the engine's slot state says at each decode step's dispatch:
    active slots and their context tokens (with ``capped``, per paged kind
    and at most the kind's ring length, summed over the kinds)."""
    decode_once = engine._decode_once

    def wrapped(*a, **kw):
        active = np.nonzero(engine._active_np)[0]
        ctx = [engine._slot_meta[s]["req"].s0 + len(engine._slot_tokens[s])
               for s in active]
        kv = (sum(min(c, t) for t in engine.ring_len.values() for c in ctx)
              if capped else sum(ctx))
        records.append((len(active), kv))
        return decode_once(*a, **kw)

    engine._decode_once = wrapped


def _tree(spans):
    """The spans under the last obs:serve/run, and that run."""
    run = [s for s in spans if s.name == "obs:serve/run"][-1]
    parent = {s.span_id: s.parent_id for s in spans}

    def under(sid):
        while sid is not None:
            if sid == run.span_id:
                return True
            sid = parent.get(sid)
        return False

    return [s for s in spans if s is not run and under(s.span_id)], run


@pytest.fixture(scope="module")
def served():
    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=3, max_len=24, page_size=4)
    records: list = []
    _slot_state(engine, records)
    obs.clear_spans()
    report = engine.run(_requests(cfg.vocab), clock="steps")
    tree, run = _tree(obs.spans())
    return types.SimpleNamespace(engine=engine, report=report, records=records,
                                 tree=tree, run=run, vocab=cfg.vocab)


def _named(tree, name):
    return [s for s in tree if s.name == name]


def test_each_decode_step_is_one_step_span_with_its_children(served):
    steps = _named(served.tree, "obs:serve/step")
    assert len(steps) == len(served.records) == served.report["decode"][
        "steady_steps"] + 1
    assert served.run.parent_id is None
    assert served.run.attrs == {
        "clock": "steps", "requests": 6, "narrow_weight_bytes": 0,
        "weight_bytes": 4 * served.engine.model.num_params()}
    for s in steps:
        assert s.parent_id == served.run.span_id
        kids = [c.name for c in served.tree if c.parent_id == s.span_id]
        assert kids == ["obs:serve/dispatch", "obs:serve/readback",
                        "obs:serve/emit"]
    assert [s.attrs["step"] for s in steps] == sorted(
        {s.attrs["step"] for s in steps})


def test_step_counters_equal_the_slot_state_at_dispatch(served):
    steps = _named(served.tree, "obs:serve/step")
    got = [(s.attrs["active"], s.attrs["kv_live_tokens"]) for s in steps]
    assert got == served.records
    engine = served.engine
    gathered = engine.max_batch * sum(engine.ring_len.values())
    assert all(s.attrs["kv_gathered_tokens"] == gathered for s in steps)
    assert sum(a for a, _ in got) == served.report["decode"][
        "steady_tokens"] + got[0][0]


def test_live_tokens_count_each_kinds_ring():
    """Sliding-window and global layers: a slot's context counts once per
    paged kind, at most that kind's ring, like the gathered tokens."""
    cfg = get_arch("gemma2_27b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=2, max_len=24, page_size=4)
    assert engine.ring_len == {"attn": 24, "swa": cfg.sliding_window}
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, (s0,)).astype(
        np.int32), max_new=k, arrival=0.0)
        for i, (s0, k) in enumerate([(14, 8), (6, 3)])]
    records, uncapped = [], []
    _slot_state(engine, uncapped)
    _slot_state(engine, records, capped=True)
    obs.clear_spans()
    engine.run(reqs, clock="steps")
    tree, _ = _tree(obs.spans())
    steps = _named(tree, "obs:serve/step")
    assert [(s.attrs["active"], s.attrs["kv_live_tokens"]) for s in steps] \
        == records
    # the long request's context passes the window: the cap is exercised
    assert any(kv < 2 * ctx for (_, kv), (_, ctx) in zip(records, uncapped))
    assert all(s.attrs["kv_gathered_tokens"] == 2 * (24 + cfg.sliding_window)
               for s in steps)
    assert all(kv <= s.attrs["kv_gathered_tokens"] for (_, kv), s
               in zip(records, steps))


def test_admission_spans_name_their_lifecycle_records(served):
    admits = _named(served.tree, "obs:serve/admit")
    recs = [r for r in served.engine.sink.records("trace")
            if r["event"] == "admitted"]
    assert [(a.attrs["rid"], a.attrs["slot"], a.attrs["pages"])
            for a in admits] == [(r["rid"], r["slot"], r["pages"])
                                 for r in recs]
    by_rid = {r.rid: r for r in _requests(served.vocab)}
    for a in admits:
        s0 = by_rid[a.attrs["rid"]].s0
        assert a.attrs["prompt_tokens"] == s0 - 1
        kids = [c.name for c in served.tree if c.parent_id == a.span_id]
        # a one-token prompt clears the slot and has no prefill to wait for
        want = ["obs:serve/slot_write", "obs:serve/admit_call"]
        want += [] if s0 == 1 else ["obs:serve/admit_wait"]
        assert kids == want + ["obs:serve/slot_write"]


def test_report_seconds_are_the_spans_durations(served):
    tree, rep = served.tree, served.report

    def child_s(parent, *names):
        return sum(c.dur_ns for c in tree
                   if c.parent_id == parent.span_id and c.name in names) / 1e9

    steps = _named(tree, "obs:serve/step")
    per_step = [child_s(s, "obs:serve/dispatch", "obs:serve/readback")
                for s in steps]
    assert rep["decode"]["compile_s"] == pytest.approx(per_step[0])
    assert rep["decode"]["steady_s"] == pytest.approx(sum(per_step[1:]))
    per_admit = [child_s(a, "obs:serve/admit_call", "obs:serve/admit_wait")
                 for a in _named(tree, "obs:serve/admit")]
    assert rep["prefill"]["compile_s"] + rep["prefill"]["steady_s"] == \
        pytest.approx(sum(per_admit))
    prefill = [r["dur_s"] for r in served.engine.sink.records("trace")
               if r["event"] == "prefill"]
    np.testing.assert_allclose(prefill, per_admit)


# -- the decode program ----------------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_decode_program_scopes_the_paged_gather_and_keeps_its_name(quantized):
    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=2, max_len=16, page_size=4,
                         quantized=quantized)
    lowered = engine._step_fn.lower(params, engine._carry, engine._tables)
    assert lowered.as_text().startswith("module @jit_step")
    hlo = lowered.compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("kv_gather", "attend", "kv_write"):
        # nested inside the decode scope, next to the pool write
        assert any(re.match(rf"jit\(step\)/obs:serve/decode/.*obs:serve/{scope}/", n)
                   for n in names), scope
    for scope in ("sample", "carry"):
        assert any(n.startswith(f"jit(step)/obs:serve/{scope}/") for n in names)


# -- with the profiler on ------------------------------------------------------------

def _host_events(xplane, prefix):
    """{name: [(start_ns, stats)]} of the host plane's events under prefix,
    times counted from the profile's start."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def profiled(served, tmp_path_factory):
    """A second run of the warm engine under a CPU profile."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    engine = served.engine
    n_before = len(engine.sink.records("trace"))
    reqs = [Request(rid=100 + r.rid, prompt=r.prompt, max_new=r.max_new,
                    arrival=r.arrival) for r in _requests(served.vocab, n=3)]
    records: list = []
    del engine._decode_once                 # the served fixture's wrapper
    _slot_state(engine, records)
    obs.clear_spans()
    with jax.profiler.trace(log_dir):
        engine.run(reqs, clock="steps")
    spans = obs.spans()
    tree, run = _tree(spans)
    pdir = glob.glob(os.path.join(log_dir, "plugins", "profile", "*"))[0]
    return types.SimpleNamespace(
        spans=spans, tree=tree, run=run, records=records,
        lifecycle=engine.sink.records("trace")[n_before:],
        json=glob.glob(os.path.join(pdir, "*.trace.json.gz"))[0],
        xplane=glob.glob(os.path.join(pdir, "*.xplane.pb"))[0])


def test_spans_stay_whole_with_the_profiler_on(profiled):
    steps = _named(profiled.tree, "obs:serve/step")
    assert [(s.attrs["active"], s.attrs["kv_live_tokens"]) for s in steps] \
        == profiled.records
    for s in steps:
        kids = [c.name for c in profiled.tree if c.parent_id == s.span_id]
        assert kids == ["obs:serve/dispatch", "obs:serve/readback",
                        "obs:serve/emit"]
    # the profiler saw each step as a step annotation with its counters
    ev = _host_events(profiled.xplane, "obs:serve/step")["obs:serve/step"]
    assert [(st["step_num"], st["active"], st["kv_live_tokens"])
            for _, st in ev] == [(s.attrs["step"], s.attrs["active"],
                                  s.attrs["kv_live_tokens"]) for s in steps]


def _profile_start_ns(xplane):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "Task Environment":
            return int(dict(plane.stats)["profile_start_time"])
    raise AssertionError("no profile_start_time")


def test_span_anchor_maps_spans_onto_the_profiles_clock(profiled):
    """A span's ``span_wall_ns`` less the profile's ``profile_start_time`` is
    where the profiler put its own annotation of that span, within 1 ms."""
    start = _profile_start_ns(profiled.xplane)
    host = _host_events(profiled.xplane, "obs:serve/")
    for name in ("obs:serve/run", "obs:serve/step", "obs:serve/admit",
                 "obs:serve/readback"):
        mine = [obs.span_wall_ns(s.start_ns) - start
                for s in profiled.spans if s.name == name]
        theirs = [t for t, _ in host[name]]
        assert len(mine) == len(theirs) > 0, name
        np.testing.assert_allclose(mine, theirs, atol=1e6, rtol=0)


_PROFILED_RUN = """
import sys
import time
import jax
import numpy as np
from repro import obs
from repro.configs import get_arch
from repro.models import TransformerLM
from repro.serve import Request, ServeEngine

log_dir = sys.argv[1]
cfg = get_arch("qwen2_0_5b", smoke=True)
model = TransformerLM(cfg)
params = model.init(jax.random.PRNGKey(0))
sink = obs.MetricsSink(log_dir)
engine = ServeEngine(model, params, max_batch=2, max_len=16, page_size=4,
                     sink=sink)
rng = np.random.default_rng(0)

def reqs(base):
    return [Request(rid=base + i, max_new=k, arrival=float(a),
                    prompt=rng.integers(0, cfg.vocab, (s0,)).astype(np.int32))
            for i, (s0, k, a) in enumerate([(6, 3, 0), (6, 2, 1), (1, 2, 2)])]

engine.run(reqs(0), clock="steps")            # compiles, unprofiled
with obs.profile(log_dir):
    # the profile starts well before the run it holds
    jax.numpy.ones(8).block_until_ready()
    time.sleep(0.05)
    engine.run(reqs(100), clock="steps")
sink.close()
"""


def test_report_cli_merges_a_profile_from_another_process(tmp_path):
    """``python -m repro.obs report --export-trace`` runs after the profiled
    run, in a process of its own: the lifecycle records still land on the
    profile's clock, each admission within 1 ms of the profiler's
    annotation of that admission."""
    from repro.obs.report import main

    log_dir = str(tmp_path / "run")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", _PROFILED_RUN, log_dir], env=env,
                   check=True, timeout=600)
    out = str(tmp_path / "merged.json.gz")
    assert main(["report", log_dir, "--export-trace", out]) == 0
    with gzip.open(out, "rt") as f:
        merged = json.load(f)["traceEvents"]
    prof = obs.find_perfetto_trace(log_dir)
    with gzip.open(prof, "rt") as f:
        base = json.load(f)["traceEvents"]
    assert merged[:len(base)] == base          # the profile's events survive
    ours = merged[len(base):]
    annotated = {int(e["args"]["rid"]): e["ts"] for e in base
                 if e.get("ph") == "X" and e.get("name") == "obs:serve/admit"}
    admitted = {e["args"]["rid"]: e["ts"] for e in ours
                if e["name"] == "admitted" and e["args"]["rid"] >= 100}
    assert sorted(admitted) == sorted(annotated) == [100, 101, 102]
    for rid, ts in admitted.items():
        assert abs(ts - annotated[rid]) < 1e3, rid


# -- the benchmark's span readers ----------------------------------------------------

def _reader(name):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "test_reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cell_ctx(workload, counts):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bj = json.load(f)
    cell = next(w for w in bj["workloads"] if w["name"] == workload)
    cfg_file = next(c["file"] for c in bj["configs"]
                    if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_file)) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        job = json.load(f)
    return types.SimpleNamespace(kind=job["kind"], cfg=cfg, job=job,
                                 counts=counts)


def _replay(tree, old_run):
    """Append a run's spans to the ring again, under a new run span."""
    with obs.host_scope("obs:serve/run", clock="steps") as run:
        for s in tree:
            profiler._RING.append(s._replace(
                parent_id=run.span_id if s.parent_id == old_run.span_id
                else s.parent_id))


def test_benchmark_readers_read_the_engines_spans(served, monkeypatch):
    obs.clear_spans()
    _replay(served.tree, served.run)
    steps = _named(served.tree, "obs:serve/step")
    live = sum(s.attrs["kv_live_tokens"] for s in steps)
    gathered = sum(s.attrs["kv_gathered_tokens"] for s in steps)
    long_ctx = _cell_ctx("serve.qwen2-0.5b.long", {})
    chat_ctx = _cell_ctx("serve.h2o-danube-1.8b.chat", {})
    share = _reader("decode_kv_live_share.tpot")
    assert share.read(long_ctx) == pytest.approx(100.0 * live / gathered)
    readback = {s.parent_id: s.dur_ns for s in served.tree
                if s.name == "obs:serve/readback"}
    want = np.mean([s.dur_ns - readback[s.span_id] for s in steps]) / 1e6
    assert _reader("decode_host_ms.tpot").read(long_ctx) == pytest.approx(want)
    admit = _reader("admit_host_ms.tput")
    assert admit.read(chat_ctx) > 0
    assert _reader("admit_host_ms.ttft").read(long_ctx) == admit.read(chat_ctx)
    # each is read only where its end-to-end metric is reported
    assert share.read(chat_ctx) is None
    assert _reader("admit_host_ms.ttft").read(chat_ctx) is None
    assert share.read(_cell_ctx("train.qwen2-0.5b.k2-complete", {})) is None
    # a program without the span ring gives nothing to read
    monkeypatch.delattr(obs, "spans")
    assert share.read(long_ctx) is None
    assert _reader("admit_host_ms.ttft").read(long_ctx) is None


def _broken_rings():
    """Rings the span readers must refuse, each with what is wrong."""
    def lost_spans():
        with obs.host_scope("obs:serve/run"):
            for i in range(6):
                with obs.host_scope("obs:serve/step", step=i, active=1,
                                    kv_live_tokens=1, kv_gathered_tokens=2):
                    with obs.host_scope("obs:serve/readback"):
                        pass
        assert obs.dropped_spans() > 0

    def no_run():
        with obs.host_scope("obs:serve/step", step=0, active=1,
                            kv_live_tokens=1, kv_gathered_tokens=2):
            pass

    def no_readback():
        with obs.host_scope("obs:serve/run"):
            with obs.host_scope("obs:serve/step", step=0, active=1,
                                kv_live_tokens=1, kv_gathered_tokens=2):
                pass

    def no_admit_wait():
        with obs.host_scope("obs:serve/run"):
            with obs.host_scope("obs:serve/admit", rid=0, prompt_tokens=5):
                pass

    def no_steps():
        with obs.host_scope("obs:serve/run"):
            with obs.host_scope("obs:serve/admit", rid=0, prompt_tokens=0):
                pass

    return {"lost_spans": lost_spans, "no_run": no_run,
            "no_readback": no_readback, "no_admit_wait": no_admit_wait,
            "no_steps": no_steps}


@pytest.mark.parametrize("broken", sorted(_broken_rings()))
def test_benchmark_readers_stop_on_missing_spans(broken, monkeypatch):
    monkeypatch.setattr(profiler, "_RING", obs.SpanRing(8))
    _broken_rings()[broken]()
    long_ctx = _cell_ctx("serve.qwen2-0.5b.long", {})
    readers = {"lost_spans": "decode_kv_live_share.tpot",
               "no_run": "decode_kv_live_share.tpot",
               "no_readback": "decode_host_ms.tpot",
               "no_admit_wait": "admit_host_ms.ttft",
               "no_steps": "decode_kv_live_share.tpot"}
    with pytest.raises(RuntimeError):
        _reader(readers[broken]).read(long_ctx)
    if broken == "no_steps":
        # a one-token prompt has no prefill to wait for
        assert _reader("admit_host_ms.ttft").read(long_ctx) >= 0


def test_kv_gather_reader_needs_the_scope(monkeypatch):
    mod = _reader("kv_gather_ms.tpot")
    ctx = _cell_ctx("serve.qwen2-0.5b.long", {"decode_steps": [(1, 2)] * 4})
    ctx.trace, ctx.devices, ctx.window = None, [0], (0, 1)
    monkeypatch.setattr(mod, "scope_ns", lambda *a: 8e6)
    assert mod.read(ctx) == pytest.approx(2.0)
    assert mod.read(_cell_ctx("serve.h2o-danube-1.8b.chat", ctx.counts)) is None
    monkeypatch.setattr(mod, "scope_ns", lambda *a: 0.0)
    with pytest.raises(RuntimeError):
        mod.read(ctx)
    # a program older than the span ring has no such scope: left out
    monkeypatch.delattr(obs, "spans")
    assert mod.read(ctx) is None
