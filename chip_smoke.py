#!/usr/bin/env python3
"""Chip smoke test: the serving engine and DR-DSGD training on a TPU at
qwen2-0.5b's published width (random weights made from ``--seed``).

Run from the root of a checkout, in one process that owns the chip:

    python3 chip_smoke.py                 # one chip: device, serve, train
    python3 chip_smoke.py --four-chips    # four chips: K=4, one node per chip

Phases (each raises on failure; nothing is caught):

* device — a TPU must be what JAX finds; otherwise the script exits
  non-zero and prints no result.  Turns the compile cache on.
* serve  — ``ServeEngine`` over a seeded Poisson trace (prompts of 32 and
  96 tokens, up to 32 new), once with the f32 and once with the int8 KV
  pool.  Every request completes, and every greedy token is the argmax of
  the teacher-forced ``model.logits_all`` reference (highest matmul
  precision), or within ``TOL_F32`` / ``TOL_INT8`` logits of it (near
  ties).  The int8 decode program must hold the Pallas kernel.
* train  — ``TrainerSpec(num_nodes=2, graph="complete")`` with dense
  mixing, batch 1 per node, seq 512, two ``run_segments`` calls of 3
  steps: finite losses and DR weights, parameters that move, and no
  compile in the second segment.
* four chips (``--four-chips``, instead of the two above) — K=4 on a
  ``("node",)`` mesh, ring graph: the ppermute gossip lowering against the
  dense lowering of the same run over 3 steps, then the int8
  error-feedback wire served by the Pallas kernel.

Times are host-clock smoke timings, not benchmarks.  The last line of
stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: teacher-forced reference tolerance (logits) for near ties
TOL_F32 = 0.1
TOL_INT8 = 0.2
#: gossip vs dense lowering after 3 steps
PARAM_ATOL = 1e-4
LOSS_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what) -> None:
    """Fail the run (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_phase(chips: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX found {dev.platform!r}); "
                 "this script only runs on a TPU")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chips, found {len(devs)}")
    from repro.utils.compile_cache import enable_compile_cache

    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} compile_cache={enable_compile_cache()}")
    return dev


def peak_gb(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 1e9


# -- serve ---------------------------------------------------------------------

def serve_trace(vocab: int, seed: int):
    from repro.serve import TrafficClass, poisson_trace

    classes = (TrafficClass("p32", prompt_len=32, gen_min=8, gen_max=32),
               TrafficClass("p96", prompt_len=96, gen_min=8, gen_max=32))
    return poisson_trace(classes, rate=0.5, horizon=16.0, vocab=vocab,
                         seed=seed)


def teacher_forced_gaps(gaps_fn, params, trace, tokens, length: int):
    """Per generated token: max logit − logit of the token the engine chose,
    from one full forward of prompt + generation (highest precision)."""
    n = len(trace)
    g = max(r.max_new for r in trace)
    toks = np.zeros((n, length), np.int32)
    pos = np.zeros((n, g), np.int32)
    chosen = np.zeros((n, g), np.int32)
    valid = np.zeros((n, g), bool)
    for i, r in enumerate(trace):
        gen = tokens[r.rid]
        seq = np.concatenate([r.prompt, gen])
        toks[i, :len(seq)] = seq
        pos[i, :len(gen)] = r.s0 - 1 + np.arange(len(gen))
        chosen[i, :len(gen)] = gen
        valid[i, :len(gen)] = True

    with jax.default_matmul_precision("highest"):
        gap, std = gaps_fn(params, jnp.asarray(toks), jnp.asarray(pos),
                           jnp.asarray(chosen))
    return np.asarray(gap)[valid], np.asarray(std)[valid]


def serve_phase(model, params, dev, *, seed: int, max_batch: int = 8,
                page_size: int = 16, require_kernel: bool = True) -> None:
    from repro.serve import ServeEngine

    trace = serve_trace(model.cfg.vocab, seed)
    max_len = max(r.s0 + r.max_new - 1 for r in trace)
    log(f"[serve] {model.cfg.name}: {model.num_params():,} params, "
        f"{len(trace)} requests (prompts {sorted({r.s0 for r in trace})}, "
        f"max_new <= {max(r.max_new for r in trace)}), max_batch={max_batch} "
        f"max_len={max_len}")

    @jax.jit
    def gaps_fn(params, toks, pos, chosen):
        logits = model.logits_all(params, {"tokens": toks})
        rows = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        picked = jnp.take_along_axis(rows, chosen[:, :, None], axis=2)[..., 0]
        return rows.max(-1) - picked, rows.std(-1)

    out = {}
    for quantized, tol in ((False, TOL_F32), (True, TOL_INT8)):
        pool = "int8" if quantized else "f32"
        engine = ServeEngine(model, params, max_batch=max_batch,
                             max_len=max_len, page_size=page_size,
                             quantized=quantized, seed=seed)
        t0 = time.perf_counter()
        rep = engine.run(list(trace), clock="steps")
        wall = time.perf_counter() - t0
        tokens = {c.rid: c.tokens for c in rep["completions"]}
        check(rep["completed"] == len(trace), (pool, rep["completed"]))
        for r in trace:
            check(len(tokens[r.rid]) == r.max_new, (pool, r.rid))
        check(rep["programs"]["serve_decode_step"] == 1, rep["programs"])
        gap, std = teacher_forced_gaps(gaps_fn, params, trace, tokens,
                                       length=max_len + 1)
        exact = int(np.sum(gap <= 0.0))
        log(f"[serve:{pool}] completed {rep['completed']}/{len(trace)} in "
            f"{rep['steps']} steps, {wall:.1f} s wall; decode compile "
            f"{rep['decode']['compile_s']:.1f} s, steady "
            f"{rep['decode']['steady_s'] / max(rep['decode']['steady_steps'], 1):.4f}"
            f" s/step; prefill compile {rep['prefill']['compile_s']:.1f} s "
            "(host-clock smoke timings, not a benchmark)")
        log(f"[serve:{pool}] teacher-forced argmax: {exact}/{gap.size} tokens "
            f"exact, max gap {gap.max():.4f} logits (tol {tol}), logit std "
            f"{std.mean():.3f}")
        check(np.all(gap <= tol), (pool, "max gap", float(gap.max())))
        if quantized:
            text = engine._step_fn.lower(
                engine.params, engine._carry,
                engine._tables).compile().as_text()
            has = "tpu_custom_call" in text
            log(f"[serve:int8] tpu_custom_call in the decode program: {has}")
            if require_kernel:
                check(has, "int8 decode program holds no Pallas kernel")
        out[pool] = tokens
        del engine
    same = sum(int(np.array_equal(out["f32"][r.rid], out["int8"][r.rid]))
               for r in trace)
    agree = np.mean(np.concatenate([
        out["f32"][r.rid] == out["int8"][r.rid] for r in trace]))
    log(f"[serve] int8 vs f32 pool: {same}/{len(trace)} requests identical, "
        f"{agree:.3f} of tokens agree position by position; peak "
        f"{peak_gb(dev):.2f} GB")


# -- train ---------------------------------------------------------------------

def _token_batches(cfg, k: int, seq: int, seed: int):
    from repro.data import make_node_token_streams

    streams = make_node_token_streams(k, cfg.vocab, seed=seed)

    def sample_batch(step):
        return {"tokens": np.stack([s.next_batch(1, seq) for s in streams])}

    return sample_batch


def _probe(params):
    """Host copy of a fixed slice of every leaf (parameters are donated)."""
    return [np.asarray(x.reshape(-1)[:4096])
            for x in jax.tree.leaves(params)]


def train_phase(model, dev, *, seed: int, k: int = 2, seq: int = 512,
                seg: int = 3) -> None:
    from repro.core import TrainerSpec, run_segments
    from repro.obs import MetricsSink, RecompileWatchdog

    sink = MetricsSink(vector_every=1)
    spec = TrainerSpec(num_nodes=k, graph="complete", lr=0.01,
                       grad_clip=1.0, seed=seed)
    trainer = spec.build(model.loss, obs=sink)
    state = trainer.init(model.init(jax.random.PRNGKey(seed)))
    before = _probe(state.params)
    sample_batch = _token_batches(model.cfg, k, seq, seed)
    log(f"[train] DR-DSGD K={k} graph=complete dense mixing, batch 1/node, "
        f"seq {seq}, 2 segments of {seg} steps")
    watch = RecompileWatchdog(label="chip_smoke train")
    watch.track("run", trainer._run, allowed=1)
    losses, secs = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        state = run_segments(
            trainer, state, sample_batch, seg, seg,
            lambda step, st, ms: losses.append(np.asarray(ms["loss_mean"])))
        jax.block_until_ready(state.params)
        secs.append(time.perf_counter() - t0)
        check(watch.check()["run"] == 1, "the second segment recompiled")
    after = _probe(state.params)
    moved = sum(int(not np.array_equal(a, b)) for a, b in zip(before, after))
    loss = np.concatenate(losses)
    recs = sink.records("train")
    dr = np.asarray([r["dr_weights"] for r in recs])
    log(f"[train] loss_mean per step {np.round(loss, 4).tolist()}")
    log(f"[train] dr_weights last step {np.round(dr[-1], 4).tolist()}; "
        f"{moved}/{len(before)} parameter leaves moved")
    check(loss.shape == (2 * seg,) and np.all(np.isfinite(loss)), "losses")
    check(dr.shape == (2 * seg, k) and np.all(np.isfinite(dr)), "DR weights")
    check(moved > len(before) // 2, ("leaves moved", moved, len(before)))
    log(f"[train] first segment {secs[0]:.1f} s, second {secs[1]:.2f} s: "
        f"compile ~{secs[0] - secs[1]:.1f} s, steady {secs[1] / seg:.3f} "
        "s/step (host-clock smoke timings, not a benchmark); programs "
        f"{watch.snapshot()}; peak {peak_gb(dev):.2f} GB")


# -- four chips ----------------------------------------------------------------

def _node_sharded(state, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = mesh.shape["node"]

    def put(x):
        spec = P("node") if getattr(x, "ndim", 0) and x.shape[0] == k else P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, state)


def _one_node_per_device(params, mesh) -> None:
    devices = {d.id for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        check({s.device.id for s in shards} == devices, "nodes share a device")
        for s in shards:
            check(s.data.shape[0] == 1, ("shard shape", s.data.shape))
        check(len({s.index[0].start for s in shards}) == len(devices),
              "a node is on more than one device")


def four_chip_phase(model, params, *, seed: int, k: int = 4, seq: int = 512,
                    steps: int = 3) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm import CompressionConfig
    from repro.core import TrainerSpec, make_gossip_mixer
    from repro.graphs import (build_graph, metropolis_weights,
                              permutation_decomposition)
    from repro.launch.mesh import make_auto_mesh

    mesh = make_auto_mesh((k,), ("node",))
    decomp = permutation_decomposition(
        metropolis_weights(build_graph("ring", k)))
    specs = jax.tree.map(lambda _: P("node"), params)
    sample_batch = _token_batches(model.cfg, k, seq, seed)
    batches = jax.device_put(
        {"tokens": np.stack([sample_batch(i)["tokens"]
                             for i in range(steps)])},
        NamedSharding(mesh, P(None, "node")))
    # every node starts at the same point, built on its own device (anew
    # for each run: a run donates its state)
    replicate = jax.jit(
        lambda p: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), p),
        out_shardings=NamedSharding(mesh, P("node")))
    log(f"[4chip] K={k} on a ('node',) mesh of {k} devices, ring graph, "
        f"batch 1/node, seq {seq}, {steps} steps")

    def run(label, mixer=None, compress="none"):
        spec = TrainerSpec(num_nodes=k, graph="ring", lr=0.01, grad_clip=1.0,
                           seed=seed, compress=compress)
        trainer = spec.build(model.loss, mixer=mixer)
        state = _node_sharded(trainer.init_stacked(replicate(params)), mesh)
        t0 = time.perf_counter()
        compiled = trainer._run.lower(state, batches).compile()
        t1 = time.perf_counter()
        state, ms = compiled(state, batches)
        jax.block_until_ready(state.params)
        t2 = time.perf_counter()
        loss = np.asarray(ms["loss_mean"])
        _one_node_per_device(state.params, mesh)
        log(f"[4chip:{label}] loss_mean {np.round(loss, 5).tolist()}; "
            f"compile {t1 - t0:.1f} s, {steps} steps {t2 - t1:.2f} s "
            "(host-clock smoke timing); one node per device")
        check(np.all(np.isfinite(loss)), (label, "losses"))
        return state, loss, compiled.as_text()

    dense, dense_loss, _ = run("dense")
    dense_params = jax.tree.map(np.asarray, dense.params)
    del dense
    gossip, gossip_loss, text = run(
        "gossip", make_gossip_mixer(decomp, mesh, "node", specs))
    check("collective-permute" in text, "gossip program has no ppermute")
    dmax = max(float(np.max(np.abs(np.asarray(g) - d)))
               for g, d in zip(jax.tree.leaves(gossip.params),
                               jax.tree.leaves(dense_params)))
    lrel = float(np.max(np.abs(gossip_loss - dense_loss)
                        / np.abs(dense_loss)))
    log(f"[4chip] gossip vs dense: max |dparam| {dmax:.3e} (atol "
        f"{PARAM_ATOL}), max loss rel diff {lrel:.3e} (rtol {LOSS_RTOL})")
    check(dmax <= PARAM_ATOL and lrel <= LOSS_RTOL, "gossip vs dense")
    del gossip, dense_params

    cc = CompressionConfig(kind="int8", use_kernel=True, seed=seed)
    _, _, text = run("int8-ef-kernel",
                     make_gossip_mixer(decomp, mesh, "node", specs, cc),
                     compress=cc)
    s8 = [ln for ln in text.splitlines()
          if "collective-permute" in ln and "s8[" in ln]
    log(f"[4chip:int8-ef-kernel] s8 collective-permutes: {len(s8)}; "
        f"tpu_custom_call: {'tpu_custom_call' in text}")
    check(s8 and "tpu_custom_call" in text,
          "int8 EF wire: no s8 collective-permute or no Pallas kernel")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the K=4 one-node-per-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = device_phase(4 if args.four_chips else 1)

    from repro.configs import get_arch
    from repro.models import TransformerLM

    model = TransformerLM(get_arch("qwen2_0_5b"))
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.four_chips:
        four_chip_phase(model, params, seed=args.seed)
    else:
        serve_phase(model, params, dev, seed=args.seed)
        del params  # the train phase holds K copies of its own
        train_phase(model, dev, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
