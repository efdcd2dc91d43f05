"""The general runner of a training job (``kind: train``).

Set-up builds one trainer and its state from the seed's weights, and drives
it through its first segment with the window's own call
(``DecentralizedTrainer.run`` on a stacked segment of the token feed): that
compiles the segment program and gives the numbers that the reference
checks.  The window then runs whole segments, each sampled on the host while
the device runs the one before, until ``--seconds`` have passed, and ends in
``block_until_ready``.

``correct`` compares the first segment with the plain reference
(``bench/reference``) on the same weights and rows: each step's mean and
worst node loss, and each parameter leaf's change over the segment, node by
node.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, program
from bench.traffic.gen import TokenFeed
from bench.weights import flatten, make_params


def _leaf_change(params, params0):
    """{leaf: (K,) norm of theta_k - theta_0}."""
    flat, flat0 = flatten(params), flatten(params0)
    return {k: jnp.sqrt(jnp.sum(jnp.square(flat[k] - flat0[k][None]),
                                axis=tuple(range(1, flat[k].ndim))))
            for k in flat}


def build(spec: common.Spec, model, hooks=None):
    from repro.core import RobustConfig, TrainerSpec

    job = spec.job
    hooks = hooks or {}
    if RobustConfig().loss_clip != job["loss_clip"]:
        raise ValueError("the job's loss_clip is not the one the trainer uses")
    if job["optimizer"] != "sgd" or job["compress"] != "none" \
            or job["lowering"] != "dense":
        raise ValueError("this runner drives SGD with dense uncompressed mixing")
    ts = TrainerSpec(num_nodes=job["nodes"], graph=job["graph"],
                     mixing=job["mixing"], mu=job["mu"], lr=job["lr"],
                     grad_clip=job["grad_clip"],
                     seed=program.program_seed(spec.seed))
    loss_fn = hooks["loss"](model) if "loss" in hooks else model.loss
    mixer = hooks["mixer"](job) if "mixer" in hooks else None
    trainer = ts.build(loss_fn, mixer=mixer)
    if "trainer" in hooks:
        hooks["trainer"](trainer)
    return trainer


def start(spec: common.Spec, devs, hooks=None, trainer=None, phases=None,
          t_start: float = 0.0):
    """Set-up up to the window: the trainer (built, or the one given), its
    state from the seed's weights, the feed, and the first segment driven
    through the window's own call.  Returns (trainer, state, feed, seg0,
    first), ``first`` holding the numbers the reference checks."""
    cfg, job = spec.cfg, spec.job
    model = program.model(cfg)
    params0 = make_params(cfg, spec.seed, device=devs[0])
    if trainer is None:
        trainer = build(spec, model, hooks)
    # committed to the chip like the state each segment returns, so that the
    # first segment's program is the one every later segment runs
    state = jax.device_put(trainer.init(params0), devs[0])
    feed = TokenFeed(job, vocab=cfg["vocab_size"], seed=spec.seed)
    seg0 = feed.segment(0)
    if phases is not None:
        jax.block_until_ready(state)
        phases["weights_state_feed"] = common.now() - t_start
    state, ms = trainer.run(state, {"tokens": jnp.asarray(seg0)})
    first = {"loss_mean": np.asarray(ms["loss_mean"], np.float64),
             "loss_worst": np.asarray(ms["loss_worst"], np.float64),
             "change": {n: np.asarray(v, np.float64) for n, v in
                        jax.jit(_leaf_change)(state.params, params0).items()}}
    return trainer, state, feed, seg0, first


def run(spec: common.Spec, devs, t_start: float, hooks=None):
    from repro.obs import RecompileWatchdog

    job = spec.job
    k, b, s, seg = (job["nodes"], job["batch_per_node"], job["seq_len"],
                    job["segment_steps"])
    phases = {"start": common.now() - t_start}
    trainer = build(spec, program.model(spec.cfg), hooks)
    watch = RecompileWatchdog(label=spec.workload)
    watch.track("run", trainer._run, allowed=1)
    trainer, state, feed, seg0, first = start(spec, devs, trainer=trainer,
                                              phases=phases, t_start=t_start)
    setup_s = common.now() - t_start

    # the window: whole segments, the next sampled while the device runs one
    losses, n_seg = [], 0
    with common.traced(spec.trace) as tr:
        t0 = common.now()
        pending = None
        while True:
            with common.span("bench:sample"):
                batch = {"tokens": jnp.asarray(feed.segment(1 + n_seg))}
            with common.span("bench:dispatch"):
                state, ms = trainer.run(state, batch)
            if pending is not None:
                with common.span("bench:wait"):
                    losses.append(np.asarray(pending))
            pending = ms["loss_mean"]
            n_seg += 1
            if common.now() - t0 >= spec.seconds:
                break
        with common.span("bench:wait"):
            losses.append(np.asarray(pending))
            jax.block_until_ready(state.params)
        t1 = common.now()
    window_s = t1 - t0
    steps = n_seg * seg
    tokens = steps * k * b * s
    progs = watch.check()
    device = common.device_record(devs)
    del state, trainer, ms, pending, batch
    gc.collect()

    t_check = common.now()
    gaps = compare(first, reference_run(spec.cfg, job, spec.seed, seg0, devs[0]))
    check_s = common.now() - t_check
    lim = spec.limits
    checks = {name: common.check(gaps[name], lim[name]) for name in lim}
    loss_all = np.concatenate(losses)
    failed = int(np.sum(~np.isfinite(loss_all)))
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and failed == 0 and progs["run"] == 1

    result = {"correct": bool(correct), "attempted": int(steps),
              "failed": failed, "device": device}
    if not spec.trace:
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens / window_s, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        ctx = common.Context(spec=spec, trace=tr.trace, devices=[d.id for d in devs],
                      window=tr.trace.window(), window_s=window_s,
                      counts={"steps": steps, "tokens": tokens})
        result["metrics"] = common.read_per_layer(spec, ctx)
        busy, win = common.busy_and_window(ctx)
        result["device"].update(busy_s=busy, window_s=win)
        result["breakdown"] = common.breakdown(ctx)
    result["log"] = {"setup_s": setup_s, "window_s": window_s,
                     "segments": n_seg, "programs": progs,
                     "first_loss_mean": first["loss_mean"].tolist(),
                     "setup_phases_s": phases,
                     "check_s": check_s, "worst_leaf": max(
                         gaps["per_leaf"], key=gaps["per_leaf"].get)}
    return result, checks


# -- the check --------------------------------------------------------------------

def reference_run(cfg, job, seed, seg0, device, dtype=jnp.float32, fault=None):
    """The reference job over the segment's steps, from the seed's weights.

    ``fault`` plants one of the faults the check must catch: ``half_batch``
    (each node's loss over the first half of its positions) or ``no_mix``
    (the exchange between nodes left out).  Returns per-step node losses,
    each leaf's first-step gradient norm (largest over the nodes) and each
    leaf's change per node over the segment.
    """
    from bench.reference import drdsgd

    k = job["nodes"]
    w = drdsgd.metropolis(job["graph"], k) if fault != "no_mix" else np.eye(k)
    seg0 = np.asarray(seg0)
    if fault == "half_batch":
        seg0 = seg0[..., : job["seq_len"] // 2 + 1]
    with jax.default_matmul_precision("highest"):
        p0 = make_params(cfg, seed, dtype=dtype, device=device)
        grad = jax.jit(lambda p, r: drdsgd.node_grad(cfg, job, p, r, dtype))
        sgd = jax.jit(lambda p, g, sc: jax.tree.map(
            lambda x, y: x - (job["lr"] * sc).astype(x.dtype) * y, p, g),
            donate_argnums=(0,))
        nodes = [jax.tree.map(jnp.copy, p0) for _ in range(k)]
        losses, first_g = [], None
        for t in range(seg0.shape[0]):
            ls = []
            for i in range(k):
                l, g, sc = grad(nodes[i], jnp.asarray(seg0[t, i]))
                if t == 0:
                    gn = {n: float(jnp.linalg.norm(v.astype(jnp.float32)))
                          for n, v in flatten(g).items()}
                    first_g = gn if first_g is None else {
                        n: max(first_g[n], gn[n]) for n in gn}
                nodes[i] = sgd(nodes[i], g, sc)
                ls.append(float(l))
                del g
            losses.append(ls)
            nodes = _mix(nodes, w)
        change = {}
        flat0 = flatten(p0)
        for i in range(k):
            for n, v in flatten(nodes[i]).items():
                d = float(jnp.linalg.norm((v.astype(jnp.float32)
                                           - flat0[n].astype(jnp.float32))))
                change.setdefault(n, np.zeros(k))[i] = d
    return {"losses": np.asarray(losses, np.float64), "first_grad": first_g,
            "change": change}


def _mix(nodes, w):
    """theta_i <- sum_j W_ij theta_j, leaf by leaf."""
    k = len(nodes)
    flats = [flatten(n) for n in nodes]
    out = [dict() for _ in range(k)]
    for name in flats[0]:
        leaves = [f[name] for f in flats]
        for i in range(k):
            acc = sum(float(w[i, j]) * leaves[j].astype(jnp.float32)
                      for j in range(k) if w[i, j] != 0.0)
            out[i][name] = acc.astype(leaves[i].dtype)
        for f in flats:
            f[name] = None
    from bench.weights import nest

    return [nest(o) for o in out]


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, as gaps of the program from the reference.

    * ``loss_rel_gap``: over the steps, the largest of |program - reference|
      / reference for the mean and the worst node loss.
    * ``change_rel_gap``: over the leaves and nodes, the largest gap between
      the program's and the reference's norm of a leaf's change, against the
      larger of that leaf's reference norm and the median leaf's.  Leaves
      whose reference first-step gradient is under a thousandth of the
      median leaf's (a key bias under softmax) are left out.
    """
    rl = ref["losses"]
    gaps = [np.abs(prog["loss_mean"] - rl.mean(axis=1)) / np.abs(rl.mean(axis=1)),
            np.abs(prog["loss_worst"] - rl.max(axis=1)) / np.abs(rl.max(axis=1))]
    loss_gap = float(np.max(np.concatenate(gaps)))
    g = ref["first_grad"]
    med_g = float(np.median(list(g.values())))
    kept = [n for n in g if g[n] >= 1e-3 * med_g]
    med_c = float(np.median([np.median(ref["change"][n]) for n in kept]))
    per_leaf = {n: float(np.max(np.abs(prog["change"][n] - ref["change"][n])
                                / np.maximum(ref["change"][n], med_c)))
                for n in kept}
    change_gap = max(per_leaf.values())
    return {"loss_rel_gap": loss_gap, "change_rel_gap": change_gap,
            "leaves_left_out": sorted(set(g) - set(kept)), "per_leaf": per_leaf}
