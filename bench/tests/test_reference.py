"""The plain reference against the system under test at smoke size on the
CPU: the transformer's logits, loss and gradient, and a whole run of each
cell kind (the trainer's first segment against the reference DR-DSGD steps,
the engine's served tokens against the reference's logits)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import smoke  # noqa: F401  (puts src/ on the path)
from bench.harness import program, serve, train
from bench import families
from bench.reference import drdsgd
from bench.weights import flatten, make_params


@pytest.mark.parametrize("cfg", [smoke.QWEN, smoke.DANUBE], ids=["qwen", "danube"])
def test_logits_match_the_model(cfg):
    model = program.model(cfg)
    params = make_params(cfg, 5)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40).astype(np.int32)
    theirs = np.asarray(model.logits_all(params, {"tokens": jnp.asarray(toks[None])}))[0]
    ref = families.load(cfg).reference()
    ours = np.asarray(ref.logits_at(cfg, params, jnp.asarray(toks),
                                            jnp.arange(40)))
    np.testing.assert_allclose(ours, theirs, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cfg", [smoke.QWEN, smoke.DANUBE], ids=["qwen", "danube"])
def test_loss_and_gradient_match_the_model(cfg):
    model = program.model(cfg)
    params = make_params(cfg, 6)
    rows = np.random.default_rng(1).integers(0, cfg["vocab_size"], (2, 33)).astype(np.int32)
    l0, g0 = jax.value_and_grad(model.loss)(params, {"tokens": jnp.asarray(rows)})
    ref = families.load(cfg).reference()
    l1, g1 = jax.value_and_grad(
        lambda p: ref.batch_loss(cfg, p, jnp.asarray(rows)))(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    for name, a in flatten(g0).items():
        a, b = np.asarray(a), np.asarray(flatten(g1)[name])
        # float32 on the CPU: agreement to rounding, against the leaf's scale
        assert np.max(np.abs(a - b)) <= 1e-4 * np.max(np.abs(a)), name


def test_metropolis_weights():
    w = drdsgd.metropolis("complete", 2)
    np.testing.assert_allclose(w, [[0.5, 0.5], [0.5, 0.5]])
    w = drdsgd.metropolis("ring", 4)
    np.testing.assert_allclose(w.sum(axis=0), 1.0)
    np.testing.assert_allclose(np.diag(w), 1.0 / 3)


def test_train_cell_is_correct_on_cpu():
    s = smoke.spec(smoke.QWEN, smoke.train_job(),
                   smoke.limits("train.qwen2-0.5b.k2-complete"))
    result, checks = train.run(s, smoke.devices(), 0.0)
    assert result["correct"], checks
    assert checks["loss_rel_gap"]["value"] < 1e-5
    assert checks["change_rel_gap"]["value"] < 1e-3


@pytest.mark.parametrize("cfg,cell", [(smoke.QWEN, "serve.qwen2-0.5b.long"),
                                      (smoke.DANUBE, "serve.h2o-danube-1.8b.chat")],
                         ids=["qwen", "danube"])
def test_serve_cell_is_correct_on_cpu(cfg, cell):
    s = smoke.spec(cfg, smoke.SERVE_JOB, smoke.limits(cell))
    result, checks = serve.run(s, smoke.devices(), 0.0)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] == 20
    assert checks["served_mean_gap"]["value"] < 1e-5


def test_a_traced_run_keeps_to_the_jobs_trace_seconds(monkeypatch):
    """A job's ``trace_seconds`` caps a traced run's window (the profile is
    stubbed: the CPU has no device trace to read)."""
    import contextlib
    import types

    from bench.harness import common

    @contextlib.contextmanager
    def traced(enabled):
        yield types.SimpleNamespace(trace=types.SimpleNamespace(window=lambda: (0, 1)))

    monkeypatch.setattr(common, "traced", traced)
    monkeypatch.setattr(common, "read_per_layer", lambda spec, ctx: {})
    monkeypatch.setattr(common, "busy_and_window", lambda ctx: (1.0, 1.0))
    monkeypatch.setattr(common, "breakdown", lambda ctx: {})
    job = dict(smoke.train_job(), trace_seconds=0.5)
    s = smoke.spec(smoke.QWEN, job, smoke.limits("train.qwen2-0.5b.k2-complete"),
                   seconds=4.0)
    s.trace = True
    result, _ = train.run(s, smoke.devices(), 0.0)
    assert result["correct"]
    assert 0.5 <= result["log"]["window_s"] < 2.0
    s.trace = False
    result, _ = train.run(s, smoke.devices(), 0.0)
    assert result["log"]["window_s"] >= 4.0
