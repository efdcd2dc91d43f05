#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> [--seeds 1,2,...] \\
        [--control-seeds 1,2,3] [--seconds 8] [--out FILE]

In one process: for each of ``--seeds``, the program's readings (the
numbers the cell compares, from the cell's own set-up and timed path; for a
serving cell a short window at the cell's own load); and for each of
``--control-seeds`` (a serving cell: those among ``--seeds``):

* the control -- the plain reference computed in bfloat16, put in the
  program's place (training: its losses and changes against the float32
  reference; serving: at the same prompts and served tokens, the gap of the
  token that the bfloat16 reference puts first);
* training only, the faults planted in the reference put in the program's
  place: half of each node's positions left out (``half_batch``) and the
  exchange between nodes left out (``no_mix``); for the compressed wire
  also the wire's plain simulation (``bench/reference/choco.py``) with a
  neighbour dropped, no exchange, int4 levels, every block scale doubled
  and error feedback off.  A step that returns its state unchanged reads 1
  by ``change_rel_gap``'s measure and needs no run.

The benchmark's own runs never run this.  One JSON line per reading goes to
``--out`` and to stdout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def train_readings(spec, devs, seeds, control_seeds, out):
    import jax.numpy as jnp

    from bench.harness import train
    from bench.traffic.gen import TokenFeed

    trainer = first = None
    mesh = train.node_mesh(spec, devs)
    for seed in seeds:
        spec.seed = seed
        t0 = time.perf_counter()
        first = None
        trainer, state, _, _, first = train.start(spec, devs, trainer=trainer,
                                                  mesh=mesh)
        del state
        gc.collect()
        gaps = train.check(spec, first, devs, mesh)
        emit(out, {"seed": seed, "what": "program", **gaps,
                   "s": time.perf_counter() - t0})
    del trainer, first
    gc.collect()
    for seed in control_seeds:
        spec.seed = seed
        if train.compressed(spec.job):
            for what, g in choco_readings(spec, devs, dtype_low=jnp.bfloat16):
                emit(out, {"seed": seed, "what": what, **g})
            continue
        seg0 = TokenFeed(spec.job, vocab=spec.cfg["vocab_size"], seed=seed).segment(0)
        ref = train.reference_run(spec.cfg, spec.job, seed, seg0, devs[0])
        for what, kw in (("control", {"dtype": jnp.bfloat16}),
                         ("half_batch", {"fault": "half_batch"}),
                         ("no_mix", {"fault": "no_mix"})):
            r = train.reference_run(spec.cfg, spec.job, seed, seg0, devs[0], **kw)
            as_prog = {"loss_mean": r["losses"].mean(axis=1),
                       "loss_worst": r["losses"].max(axis=1),
                       "change": r["change"]}
            emit(out, {"seed": seed, "what": what, **train.compare(as_prog, ref)})


def choco_readings(spec, devs, dtype_low):
    """(what, gaps) of the compressed wire's plain simulation put in the
    program's place, clean and with each fault, and of the control; each
    against the clean simulation's float32 reference."""
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import train
    from bench.reference import drdsgd
    from bench.traffic.gen import TokenFeed

    job = spec.job
    k = job["nodes"]
    mesh = train.node_mesh(spec, devs)
    feed = TokenFeed(job, vocab=spec.cfg["vocab_size"], seed=spec.seed)
    segs = [feed.segment(0), feed.segment(1)]
    w = drdsgd.metropolis(job["graph"], k)
    dropped = w.copy()
    for i in range(0, k - 1, 2):        # each node loses one neighbour
        dropped[i, i + 1] = dropped[i + 1, i] = 0.0
    sims = {"clean": {}, "dropped_neighbour": {"w": dropped},
            "no_exchange": {"w": np.eye(k)}, "int4_wire": {"qmax": 7.0},
            "scale_doubled": {"scale": 2.0},
            "no_error_feedback": {"error_feedback": False}}
    sims = {n: {a: jnp.asarray(v, jnp.float32) if a == "w" else v
                for a, v in kw.items()} for n, kw in sims.items()}
    base = simulated_rounds(spec, segs, mesh, sims)
    runs = {n: (base, r) for n, r in base["rounds"].items()}
    for what, kw in (("half_batch", {"half_batch": True}),
                     ("control", {"dtype": dtype_low})):
        r = simulated_rounds(spec, segs, mesh, {"clean": {}}, **kw)
        runs[what] = (r, r["rounds"]["clean"])
    for what, (r, rounds) in runs.items():
        prog = {"loss_mean": r["losses"].mean(axis=1),
                "loss_worst": r["losses"].max(axis=1)}
        rounds = [{n: dict(st, ref_change=b[n]["ref_change"]) for n, st in rr.items()}
                  for rr, b in zip(rounds, base["rounds"]["clean"])]
        yield what, train.compare_choco(prog, dict(base, rounds=rounds))


def simulated_rounds(spec, segs, mesh, sims, dtype=None, half_batch=False):
    """``train.choco_reference`` with the wire's plain simulation
    (``choco.simulate_round``) in the program's place, once for each entry
    of ``sims`` ({name: its keywords}), all on the pre-mix parameters of the
    first entry's trajectory.  ``half_batch`` takes each node's loss over
    the first half of its positions.  The result's ``rounds`` is
    {name: rounds}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import train
    from bench.reference import choco, drdsgd
    from bench.weights import flatten, nest

    cfg, job, seed = spec.cfg, spec.job, spec.seed
    dtype = dtype or jnp.float32
    w = jnp.asarray(drdsgd.metropolis(job["graph"], job["nodes"]), jnp.float32)
    stat = train.round_stat(job)
    simulate = jax.jit(choco.simulate_round, static_argnames=(
        "gamma", "block_d", "qmax", "scale", "error_feedback"))
    names = list(sims)
    rounds = {n: [] for n in names}
    hats = {n: None for n in names}            # the simulations' public copies
    losses, first_g, theta = [], None, None
    with jax.default_matmul_precision("highest"):
        step = train.pre_mix_step(cfg, job, dtype)
        for t, seg in enumerate(segs):
            rows = np.asarray(seg)[0]
            if half_batch:
                rows = rows[..., : job["seq_len"] // 2 + 1]
            start = (train.seed_weights(cfg, job, seed, mesh, dtype) if t == 0
                     else nest({n: train.node_sharded(v, mesh).astype(dtype)
                                for n, v in theta.items()}))
            ls, pre, norms = step(start, train.node_sharded(rows, mesh))
            losses.append(np.asarray(ls, np.float64))
            if t == 0:
                first_g = {n: float(jnp.max(v)) for n, v in norms.items()}
            pre, start = flatten(pre), flatten(start)
            stats = {n: {} for n in names}
            new_theta, new_hats = {}, {n: {} for n in names}
            for leaf_i, leaf in enumerate(pre):
                x, st = train.node_rows(pre[leaf]), train.node_rows(start[leaf])
                key = jax.random.fold_in(jax.random.fold_in(
                    jax.random.PRNGKey(seed % 2 ** 31), t), leaf_i)
                for name in names:
                    hp = (jnp.zeros_like(x) if hats[name] is None else
                          train.node_rows(train.node_sharded(hats[name][leaf], mesh)))
                    kw = dict(sims[name])
                    xn, hn = simulate(x, hp, kw.pop("w", w), key, gamma=job["gamma"],
                                      block_d=job["block_d"], **kw)
                    if t + 1 < len(segs):       # kept for the next round
                        shape = pre[leaf].shape
                        new_hats[name][leaf] = np.asarray(hn).reshape(shape)
                        if name == names[0]:
                            new_theta[leaf] = np.asarray(xn).reshape(shape)
                    stats[name][leaf] = train.leaf_stats(stat, x, hp, hn, xn, st)
            theta, hats = new_theta, new_hats
            for name in names:
                rounds[name].append(stats[name])
            del pre, start
    return {"losses": np.asarray(losses, np.float64), "first_grad": first_g,
            "rounds": rounds}


def serve_readings(spec, devs, seeds, control_seeds, out):
    import numpy as np

    from bench.harness import program, serve
    from bench.traffic.gen import serve_requests, warmup_requests
    from bench.weights import make_params

    cfg, job = spec.cfg, spec.job
    model = program.model(cfg)
    engine = None
    for seed in seeds:
        spec.seed = seed
        t0 = time.perf_counter()
        params = make_params(cfg, seed)
        if engine is None:
            engine = serve.build(spec, model, params)
            warm = warmup_requests(job, vocab=cfg["vocab_size"], seed=seed,
                                   count=job["max_batch"])
            engine.run(serve._requests(warm), clock="steps")
        engine.params = params
        reqs = serve_requests(job, vocab=cfg["vocab_size"], seed=seed,
                              seconds=spec.seconds)
        rep = engine.run(serve._requests(reqs), clock="wall")
        served = {c.rid: np.asarray(c.tokens) for c in rep["completions"]}
        engine.params = None
        del params, rep
        gc.collect()
        g = serve.reference_gaps(spec, reqs, served, devs[0], readings=True,
                                 control=seed in control_seeds)
        emit(out, {"seed": seed, "what": "program", "requests": len(reqs),
                   "completed": len(served), **g, "s": time.perf_counter() - t0})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench.harness import common

    seeds = [int(x) for x in args.seeds.split(",") if x]
    ctl = [int(x) for x in args.control_seeds.split(",") if x]
    spec = common.resolve(args.workload, seed=(seeds + ctl)[0], seconds=args.seconds,
                          trace=False)
    common.enable_compile_cache()
    devs = common.require_chip(spec)
    fn = train_readings if spec.job["kind"] == "train" else serve_readings
    fn(spec, devs, seeds, ctl, args.out)


if __name__ == "__main__":
    main()
