"""A run with its timed path broken underneath must come out not correct.

Each test skips only the harness's look for a chip and drives the rest of a
run at smoke size on the CPU, against the cells' own limits, with one fault
planted in the program: a training step that returns its state unchanged,
half of each node's batch left out (the mean taken over the rest), the
exchange between nodes left out, and a served token altered where it is
produced.  A control test runs the plain reference computed in bfloat16 in
the program's place.

At smoke size (vocabulary 256) the node losses, about 6, lie under the
loss clip of 10, so the DR reweighting is live there and a step that swaps
the nodes' weights must fail too.  At the cell's own size the first losses
(about 12.4) lie over the clip and every node gets the same weight.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import smoke
from bench.harness import serve, train

TRAIN = "train.qwen2-0.5b.k2-complete"


def _train(hooks):
    s = smoke.spec(smoke.QWEN, smoke.train_job(), smoke.limits(TRAIN))
    return train.run(s, smoke.devices(), 0.0, hooks=hooks)


def _failed(checks):
    return [n for n, c in checks.items() if c["value"] > c["limit"]]


def test_state_left_unchanged():
    def freeze(trainer):
        run = trainer._run

        def unchanged(state, batches):
            # the program's step runs on a copy (it donates its input); the
            # state handed back is the one it was given
            _, ms = run(jax.tree.map(jnp.copy, state), batches)
            return state, ms

        unchanged._cache_size = run._cache_size
        trainer._run = unchanged

    result, checks = _train({"trainer": freeze})
    assert not result["correct"]
    assert checks["change_rel_gap"]["value"] > 0.99


def test_half_of_the_batch_left_out():
    seq = smoke.train_job()["seq_len"]

    def half(model):
        return lambda p, b: model.loss(p, {"tokens": b["tokens"][:, : seq // 2 + 1]})

    result, checks = _train({"loss": half})
    assert not result["correct"] and _failed(checks)


def test_exchange_left_out():
    from repro.core.consensus import make_identity_mixer

    result, checks = _train({"mixer": lambda job: make_identity_mixer()})
    assert not result["correct"] and _failed(checks)


def test_node_weights_swapped(monkeypatch):
    import repro.core.drdsgd as drdsgd

    scale = drdsgd.robust_scale
    monkeypatch.setattr(drdsgd, "robust_scale", lambda losses, cfg: scale(losses[::-1], cfg))
    result, checks = _train({})
    assert not result["correct"] and _failed(checks) == ["change_rel_gap"]


def test_served_token_altered():
    cell = "serve.qwen2-0.5b.long"
    vocab = smoke.QWEN["vocab_size"]

    def alter(engine):
        step = engine._step_fn

        def altered(params, carry, tables):
            carry, out = step(params, carry, tables)
            tok = jnp.where(out[0] >= 0, (out[0] + 1) % vocab, out[0])
            return carry, out.at[0].set(tok)

        engine._step_fn = altered

    s = smoke.spec(smoke.QWEN, smoke.SERVE_JOB, smoke.limits(cell))
    result, checks = serve.run(s, smoke.devices(), 0.0, hooks={"engine": alter})
    assert not result["correct"] and _failed(checks)


def test_control_fails_the_train_cell():
    s = smoke.spec(smoke.QWEN, smoke.train_job(), smoke.limits(TRAIN))
    seg0 = train.TokenFeed(s.job, vocab=s.cfg["vocab_size"], seed=s.seed).segment(0)
    dev = smoke.devices()[0]
    ref = train.reference_run(s.cfg, s.job, s.seed, seg0, dev)
    low = train.reference_run(s.cfg, s.job, s.seed, seg0, dev, dtype=jnp.bfloat16)
    gaps = train.compare({"loss_mean": low["losses"].mean(axis=1),
                          "loss_worst": low["losses"].max(axis=1),
                          "change": low["change"]}, ref)
    assert any(gaps[n] > lim for n, lim in s.limits.items()), gaps


@pytest.mark.parametrize("cfg,cell", [(smoke.QWEN, "serve.qwen2-0.5b.long"),
                                      (smoke.DANUBE, "serve.h2o-danube-1.8b.chat")],
                         ids=["qwen", "danube"])
def test_control_reads_worse_than_the_program(cfg, cell):
    """The bfloat16 reference in the program's place, at the same prompts and
    served tokens: on the CPU the program matches the float32 reference to
    rounding, and the control's tokens lie measurably below its best.  The
    smoke widths are raised (d 256, vocab 8192) so that bfloat16 moves some
    of the 65 checked tokens off the float32 argmax."""
    from bench.harness import program
    from bench.weights import make_params

    cfg = dict(cfg, hidden_size=256, intermediate_size=512, vocab_size=8192,
               head_dim=64)
    s = smoke.spec(cfg, dict(smoke.SERVE_JOB, check_requests=8), smoke.limits(cell))
    engine = serve.build(s, program.model(cfg), make_params(cfg, s.seed))
    reqs = serve.serve_requests(s.job, vocab=cfg["vocab_size"], seed=s.seed,
                                seconds=s.seconds)
    rep = engine.run(serve._requests(reqs), clock="steps")
    served = {c.rid: np.asarray(c.tokens) for c in rep["completions"]}
    g = serve.reference_gaps(s, reqs, served, smoke.devices()[0], control=True)
    assert g["served_mean_gap"] <= s.limits["served_mean_gap"]
    assert g["control_mean_gap"] > 10 * max(g["served_mean_gap"], 1e-7)
