#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--seconds 8] [--out FILE]

In one process, for each seed: the program's readings (the numbers the
cell compares, from the cell's own set-up and timed path; for a serving
cell a short window at the cell's own load) and, on the control seeds:

* the control -- the plain reference computed in bfloat16, put in the
  program's place (training: its losses and changes against the float32
  reference; serving: at the same prompts and served tokens, the gap of the
  token that the bfloat16 reference puts first);
* training only, the faults planted in the reference put in the program's
  place: half of each node's positions left out (``half_batch``) and the
  exchange between nodes left out (``no_mix``).  A step that returns its
  state unchanged reads 1 by ``change_rel_gap``'s measure and needs no run.

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

    trainer = None
    for seed in seeds:
        spec.seed = seed
        t0 = time.perf_counter()
        trainer, state, _, seg0, first = train.start(spec, devs, trainer=trainer)
        del state
        gc.collect()
        ref = train.reference_run(spec.cfg, spec.job, seed, seg0, devs[0])
        gaps = train.compare(first, ref)
        emit(out, {"seed": seed, "what": "program", **gaps,
                   "s": time.perf_counter() - t0})
        if seed not in control_seeds:
            continue
        for what, kw in (("control", {"dtype": jnp.bfloat16}),
                         ("half_batch", {"fault": "half_batch"}),
                         ("no_mix", {"fault": "no_mix"})):
            r = train.reference_run(spec.cfg, spec.job, seed, seg0, devs[0], **kw)
            as_prog = {"loss_mean": r["losses"].mean(axis=1),
                       "loss_worst": r["losses"].max(axis=1),
                       "change": r["change"]}
            emit(out, {"seed": seed, "what": what, **train.compare(as_prog, ref)})


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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench.harness import common

    seeds = [int(x) for x in args.seeds.split(",")]
    ctl = {int(x) for x in args.control_seeds.split(",") if x}
    spec = common.resolve(args.workload, seed=seeds[0], seconds=args.seconds,
                          trace=False)
    common.enable_compile_cache()
    devs = common.require_chip(spec)
    fn = train_readings if spec.job["kind"] == "train" else serve_readings
    fn(spec, devs, seeds, ctl, args.out)


if __name__ == "__main__":
    main()
