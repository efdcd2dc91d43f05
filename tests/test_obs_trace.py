"""repro.obs.trace + repro.obs.hist: in-jit streaming histograms bit-exact
vs np.histogram, host-derived trainer round events replaying the seeded
fault process, Chrome/perfetto trace export + profile merge, and the serve
engine's request-lifecycle trace as the single latency accounting."""

import gzip
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import audit_host_callbacks
from repro.core import DecentralizedTrainer, RobustConfig
from repro.dynamics import FaultConfig, replay_fault_masks
from repro.obs import (
    MetricsSink,
    TRAIN_HISTOGRAMS,
    HistSpec,
    export_chrome_trace,
    format_trace,
    hist_counts,
    merge_with_profile,
    serve_latency_summary,
    to_chrome_events,
    trainer_trace_events,
    validate_jsonl,
    validate_record,
)
from repro.obs.hist import edges, transform
from repro.obs.schema import SCHEMA_VERSION


# -- in-jit streaming histograms -----------------------------------------------

@pytest.mark.parametrize("spec", TRAIN_HISTOGRAMS,
                         ids=[s.source for s in TRAIN_HISTOGRAMS])
def test_hist_counts_bit_exact_vs_np_histogram(spec):
    """The acceptance criterion verbatim: in-jit counts equal
    ``np.histogram(x, bins=edges)`` — including values sitting exactly on
    interior edges, on ``hi`` (closed last bin) and out of range (dropped)."""
    rng = np.random.default_rng(0)
    if spec.log10:
        # raw values spanning decades around the grid, plus degenerate zeros
        x = np.concatenate([
            10.0 ** rng.uniform(spec.lo - 2, spec.hi + 1, 257),
            [0.0, 1e-30, 10.0 ** spec.lo, 10.0 ** spec.hi],
        ]).astype(np.float32)
    else:
        width = spec.hi - spec.lo
        x = np.concatenate([
            rng.uniform(spec.lo - 0.3 * width, spec.hi + 0.3 * width, 257),
            # the edge cases: lo, hi, an interior edge, just-outside
            [spec.lo, spec.hi, spec.lo + width / spec.bins,
             spec.lo - 1e-3, spec.hi + 1e-3],
        ]).astype(np.float32)
    counts = np.asarray(jax.jit(lambda v: hist_counts(v, spec))(
        jnp.asarray(x)))
    ref, _ = np.histogram(np.asarray(transform(spec, x)),
                          bins=np.asarray(edges(spec)))
    np.testing.assert_array_equal(counts, ref)
    # out-of-range values are dropped, so sum(counts) < len(x) flags overflow
    assert counts.sum() <= x.size
    assert counts.dtype == np.int32 and counts.shape == (spec.bins,)


def test_hist_spec_validates_its_grid():
    with pytest.raises(ValueError, match="hi > lo"):
        HistSpec("x", lo=1.0, hi=1.0)
    with pytest.raises(ValueError, match="bins"):
        HistSpec("x", lo=0.0, hi=1.0, bins=0)
    assert HistSpec("loss_nodes", 0.0, 8.0).field == "hist_loss_nodes"


def test_trainer_tap_with_histograms_stages_only_obs_callbacks():
    """The zero-extra-callbacks acceptance criterion: with the sink (and its
    histogram payload) enabled, every host callback in the compiled step
    comes from repro.obs — nothing else."""
    k, d, steps = 4, 3, 6

    def loss(params, batch):
        (target,) = batch
        return jnp.mean((params["w"] - target) ** 2)

    trainer = DecentralizedTrainer(loss, num_nodes=k, graph="ring", lr=0.05,
                                   robust=RobustConfig(mu=3.0),
                                   obs=MetricsSink())
    state = trainer.init({"w": jnp.zeros((d,))})
    target = jnp.linspace(-1.0, 1.0, k).reshape(k, 1) * jnp.ones((k, d))
    batches = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (steps,) + x.shape), (target,))
    assert audit_host_callbacks(trainer._run, state, batches) == []


# -- host-derived trainer round events -----------------------------------------

def _train_rec(step, **kw):
    rec = {"v": SCHEMA_VERSION, "kind": "train", "step": step,
           "loss_mean": 1.0, "loss_worst": 2.0, "loss_std": 0.1,
           "robust_objective": 1.1, "comm_bytes": 100.0,
           "wire_bits": 800.0, "ef_residual_norm": 0.01}
    rec.update(kw)
    return rec


def test_ef_rebase_and_rate_switch_detection():
    recs = [_train_rec(s, ef_rounds=s + 1,
                       wire_bits=800.0 if s < 4 else 200.0)
            for s in range(8)]
    events = trainer_trace_events(recs, ef_rebase_every=4)
    assert all(validate_record(e) == [] for e in events)
    assert [(e["step"], e["event"]) for e in events] == \
        [(3, "ef_rebase"), (4, "rate_switch"), (7, "ef_rebase")]
    switch = events[1]
    assert switch["wire_bits_old"] == 800.0
    assert switch["wire_bits_new"] == 200.0
    assert [e["ef_rounds"] for e in events if e["event"] == "ef_rebase"] \
        == [4, 8]


def test_ef_rebase_adaptive_threshold_uses_previous_drift():
    recs = [_train_rec(s, ef_rounds=s + 1, ef_drift=d)
            for s, d in enumerate([0.1, 0.9, 0.8, 0.2])]
    events = trainer_trace_events(recs, ef_rebase_threshold=0.5)
    # fires on the round AFTER the drift exceeded the threshold
    assert [(e["step"], e["ef_drift"]) for e in events] == \
        [(2, 0.9), (3, 0.8)]


def test_rate_switch_suppressed_when_link_set_varies():
    """wire_bits moves with the live link count under faults or a dynamic
    topology, so a codec rate change is not identifiable — no rate_switch
    events may be derived there."""
    recs = [_train_rec(s, wire_bits=800.0 if s < 4 else 200.0,
                       loss_nodes=[1.0] * 4)
            for s in range(8)]
    assert any(e["event"] == "rate_switch"
               for e in trainer_trace_events(recs))
    faulty = trainer_trace_events(
        recs, faults=FaultConfig(straggler_p=0.5, seed=3), num_nodes=4)
    assert not any(e["event"] == "rate_switch" for e in faulty)
    dynamic = trainer_trace_events(recs, topology="dynamic")
    assert dynamic == []


def test_fault_events_match_replayed_masks():
    """The round-trip the ISSUE names: events derived from a telemetry
    stream + FaultConfig must equal a fresh replay of the seeded fault
    process — per round, per link count, per down-node set."""
    cfg = FaultConfig(straggler_p=0.4, outage_p=0.2, outage_len=3, seed=7)
    k, steps = 6, list(range(20))
    recs = [_train_rec(s) for s in steps]
    events = {e["step"]: e
              for e in trainer_trace_events(recs, faults=cfg, num_nodes=k)}

    keep, up = replay_fault_masks(cfg, steps, k)
    iu = np.triu_indices(k, 1)
    n_fault_rounds = 0
    for i, s in enumerate(steps):
        down = np.nonzero(up[i] < 0.5)[0]
        links_down = int(np.sum(keep[i][iu] < 0.5))
        if links_down or down.size:
            n_fault_rounds += 1
            ev = events[s]
            assert ev["event"] == "fault"
            assert ev["links_down"] == links_down
            assert ev["nodes_down"] == down.size
            assert ev["down_nodes"] == [int(n) for n in down]
        else:
            assert s not in events
    assert n_fault_rounds > 0          # the config actually exercised faults
    assert len(events) == n_fault_rounds


def test_fault_replay_infers_num_nodes_or_demands_it():
    cfg = FaultConfig(straggler_p=0.5, seed=1)
    with_vec = [_train_rec(0, loss_nodes=[1.0] * 5), _train_rec(1)]
    # inferred k=5 replays without error
    trainer_trace_events(with_vec, faults=cfg)
    with pytest.raises(ValueError, match="num_nodes"):
        trainer_trace_events([_train_rec(0)], faults=cfg)


# -- trace records through the sink / schema -----------------------------------

def test_trace_records_round_trip_jsonl(tmp_path):
    sink = MetricsSink(str(tmp_path))
    sink.log("trace", 0, event="queued", rid=1, cls="chat", t_s=0.0)
    sink.log("trace", 3, event="fault", links_down=2, nodes_down=1,
             down_nodes=[4])
    sink.close()
    summary = validate_jsonl(sink.path)
    assert summary["errors"] == []
    assert summary["kinds"] == {"trace": 2}
    with open(sink.path) as f:
        back = [json.loads(line) for line in f]
    assert back[0]["event"] == "queued" and back[0]["cls"] == "chat"
    assert back[1]["down_nodes"] == [4]
    assert "fault" in format_trace(back[1])


def test_schema_rejects_malformed_trace_records():
    assert validate_record({"v": SCHEMA_VERSION, "kind": "trace",
                            "step": 0}) != []                 # no event
    assert validate_record({"v": SCHEMA_VERSION, "kind": "trace", "step": 0,
                            "event": "finished", "ttft_s": "slow"}) != []
    assert validate_record({"v": SCHEMA_VERSION, "kind": "trace", "step": 0,
                            "event": "fault", "down_nodes": [0.5]}) != []


# -- Chrome trace-event export -------------------------------------------------

def _mixed_trace_records():
    serve = [
        {"v": SCHEMA_VERSION, "kind": "trace", "step": 0, "event": "queued",
         "rid": 0, "cls": "chat", "t_s": 0.0},
        {"v": SCHEMA_VERSION, "kind": "trace", "step": 0, "event": "admitted",
         "rid": 0, "cls": "chat", "slot": 1, "pages": 2, "t_s": 0.01},
        {"v": SCHEMA_VERSION, "kind": "trace", "step": 5, "event": "finished",
         "rid": 0, "cls": "chat", "slot": 1, "tokens": 4, "t_s": 0.5,
         "dur_s": 0.49, "ttft_s": 0.2, "per_token_s": 0.05, "queued_s": 0.01},
    ]
    train = trainer_trace_events(
        [_train_rec(s, ef_rounds=s + 1) for s in range(4)],
        ef_rebase_every=2)
    return serve + train


def test_to_chrome_events_shapes_and_clocks():
    recs = _mixed_trace_records()
    evs = to_chrome_events(recs)
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in evs)
    # serve events are wall-clocked; finished also gets an admit->done span
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == 1
    assert spans[0]["dur"] == pytest.approx(0.49e6)
    assert spans[0]["ts"] == pytest.approx((0.5 - 0.49) * 1e6)
    assert spans[0]["tid"] == "slot1"
    queued = next(e for e in evs if e["name"] == "queued")
    assert queued["tid"] == "queue" and queued["ts"] == 0.0
    # trainer events land on the synthetic 1000 us/step ruler
    rebase = [e for e in evs if e["name"] == "ef_rebase"]
    assert [e["ts"] for e in rebase] == [1000.0, 3000.0]
    # non-trace records are ignored
    assert to_chrome_events([_train_rec(0)]) == []


@pytest.mark.parametrize("suffix", [".json", ".json.gz"])
def test_export_chrome_trace_writes_loadable_json(tmp_path, suffix):
    recs = _mixed_trace_records()
    path = str(tmp_path / f"trace{suffix}")
    assert export_chrome_trace(recs, path) == path
    opener = gzip.open if suffix.endswith(".gz") else open
    with opener(path, "rt") as f:
        obj = json.load(f)
    assert obj["displayTimeUnit"] == "ms"
    assert len(obj["traceEvents"]) == len(to_chrome_events(recs))


def test_merge_with_profile_offsets_onto_the_xla_timeline(tmp_path):
    """Merging must land our run-relative events on the profile's own clock:
    from the start of the profiler's annotation of the run's
    ``obs:serve/run`` span.  find_perfetto_trace locates the file
    jax.profiler.trace dumped, as launch/train.py does."""
    from repro.obs import find_perfetto_trace, host_scope

    with jax.profiler.trace(str(tmp_path)):
        jnp.ones(8).block_until_ready()
        with host_scope("obs:serve/run", clock="wall", requests=1):
            jnp.ones(8).block_until_ready()

    prof = find_perfetto_trace(str(tmp_path))
    assert prof is not None and prof.endswith(".trace.json.gz")
    recs = _mixed_trace_records()
    out = str(tmp_path / "merged.json")
    merge_with_profile(recs, prof, out)
    with gzip.open(prof, "rt") as f:
        xla = json.load(f)["traceEvents"]
    with open(out) as f:
        merged = json.load(f)["traceEvents"]
    assert merged[:len(xla)] == xla     # the profile's events survive
    ours = merged[len(xla):]
    assert len(ours) == len(to_chrome_events(recs))
    annotated = [e["ts"] for e in xla
                 if e.get("ph") == "X" and e.get("name") == "obs:serve/run"]
    assert len(annotated) == 1
    t0 = annotated[0]
    # not the profile's earliest event: the run starts after other work
    assert t0 > min(e["ts"] for e in xla if "ts" in e)
    assert all(e["ts"] >= t0 for e in ours)
    queued = next(e for e in ours if e["name"] == "queued")
    assert queued["ts"] == pytest.approx(t0)


# -- the serve engine's lifecycle trace ----------------------------------------

def test_engine_emits_request_lifecycle_and_owns_latency():
    """Every request leaves the full queued->admitted->prefill->first_token->
    finished trail, the finished record agrees with the Completion it
    mirrors, and report["latency"] is exactly serve_latency_summary over the
    engine's own trace records — one accounting, asserted."""
    from repro.configs import get_arch
    from repro.models import TransformerLM
    from repro.serve import Request, ServeEngine

    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, (6,),
                                               dtype=np.int32),
                    max_new=3, arrival=float(i),
                    cls="chat" if i % 2 == 0 else "doc")
            for i in range(4)]
    engine = ServeEngine(model, params, max_batch=2, max_len=16, page_size=4)
    report = engine.run(list(reqs), clock="steps")

    traces = engine.sink.records("trace")
    assert all(validate_record(r) == [] for r in traces)
    by_rid: dict[int, list[str]] = {}
    for r in traces:
        by_rid.setdefault(r["rid"], []).append(r["event"])
    assert set(by_rid) == {0, 1, 2, 3}
    for rid, events in by_rid.items():
        assert events == ["queued", "admitted", "prefill", "first_token",
                          "finished"], rid

    fin = {r["rid"]: r for r in traces if r["event"] == "finished"}
    for c in report["completions"]:
        rec = fin[c.rid]
        assert rec["cls"] == c.cls
        assert rec["s0"] == c.s0
        assert rec["tokens"] == c.n_tokens
        assert rec["ttft_s"] == pytest.approx(c.ttft)
        assert rec["pages"] > 0

    lat = report["latency"]
    assert lat == serve_latency_summary(traces)
    assert lat["requests"] == len(reqs)
    assert set(lat["per_class"]) == {"chat", "doc"}


def test_serve_latency_summary_rollup():
    fin = [{"kind": "trace", "event": "finished", "cls": "chat",
            "ttft_s": 0.1, "per_token_s": 0.01, "tokens": 5, "queued_s": 0.0},
           {"kind": "trace", "event": "finished", "cls": "doc",
            "ttft_s": 0.3, "tokens": 1, "queued_s": 0.1},
           {"kind": "trace", "event": "queued"}]
    lat = serve_latency_summary(fin)
    assert lat["requests"] == 2 and lat["tokens"] == 6
    assert lat["ttft_p50_s"] == pytest.approx(0.2)
    assert lat["per_token_p50_s"] == pytest.approx(0.01)
    # single-token requests have no inter-token latency to report
    assert "per_token_p50_s" not in lat["per_class"]["doc"]
    assert serve_latency_summary([]) == {"requests": 0}
