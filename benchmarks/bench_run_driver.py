"""Dispatch-overhead benchmark: `trainer.run` (lax.scan) vs the per-step loop.

The paper's headline is communication/round efficiency; realizing it in
wall-clock terms requires the hot loop to not be bottlenecked by per-step
Python dispatch. This benchmark times the same fmnist MLP DR-DSGD config
(K=10, Erdős–Rényi p=0.3, B=32) through

  * ``step``: N jitted `trainer.step` calls from Python (the pre-v2 loop),
  * ``run``:  one `trainer.run` scan program over the N stacked batches
              (donated carried state),

on identical pre-sampled batches, and reports steps/s for both plus the
speedup. Results are recorded in EXPERIMENTS.md §Run-driver.

Run:  PYTHONPATH=src python -m benchmarks.bench_run_driver [--steps 500]
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import fmt_row, make_task, stack_batches
from repro.core import TrainerSpec
from repro.models.paper_nets import make_classifier_loss
from repro.utils.compile_cache import enable_compile_cache


def bench(steps: int, batch: int, num_nodes: int, seed: int,
          compress: str) -> dict:
    fed, init_fn, apply_fn = make_task("fmnist", num_nodes, seed)
    trainer = TrainerSpec(
        num_nodes=num_nodes, graph="erdos_renyi",
        graph_kwargs={"p": 0.3, "seed": seed},
        mu=3.0, lr=0.1, grad_clip=2.0, compress=compress, seed=seed,
    ).build(make_classifier_loss(apply_fn), apply_fn)
    rng = np.random.default_rng(seed)
    stacked = stack_batches(fed, rng, batch, steps)

    # -- per-step python loop (warm one step first so jit compile is excluded)
    state = trainer.init(init_fn(jax.random.PRNGKey(seed)))
    state, m = trainer.step(state, (stacked[0][0], stacked[1][0]))
    jax.block_until_ready(m["loss_mean"])
    t0 = time.perf_counter()
    for i in range(steps):
        state, m = trainer.step(state, (stacked[0][i], stacked[1][i]))
    jax.block_until_ready(m["loss_mean"])
    t_step = time.perf_counter() - t0

    # -- scan driver (warm the same-length program, then time a fresh run)
    state = trainer.init(init_fn(jax.random.PRNGKey(seed)))
    state, ms = trainer.run(state, stacked)
    jax.block_until_ready(ms["loss_mean"])
    state = trainer.init(init_fn(jax.random.PRNGKey(seed)))
    t0 = time.perf_counter()
    state, ms = trainer.run(state, stacked)
    jax.block_until_ready(ms["loss_mean"])
    t_run = time.perf_counter() - t0

    return {
        "steps": steps,
        "steps_per_s_step_loop": steps / t_step,
        "steps_per_s_run": steps / t_run,
        "speedup": t_step / t_run,
        "us_per_step_loop": t_step / steps * 1e6,
        "us_per_step_run": t_run / steps * 1e6,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--nodes", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8"],
                    help="also time the EF-compressed consensus path")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (plumbing, not a benchmark)")
    args = ap.parse_args()
    enable_compile_cache()
    steps = 20 if args.smoke else args.steps
    r = bench(steps, args.batch, args.nodes, args.seed, args.compress)
    print(fmt_row(
        f"run_driver_{args.compress}", r["us_per_step_run"],
        f"steps={r['steps']};"
        f"steps_per_s_run={r['steps_per_s_run']:.1f};"
        f"steps_per_s_step_loop={r['steps_per_s_step_loop']:.1f};"
        f"speedup={r['speedup']:.2f}x"))


if __name__ == "__main__":
    main()
