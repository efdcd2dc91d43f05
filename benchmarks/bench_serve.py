"""BENCH_serve: open-loop latency/throughput of the continuous-batching engine.

Method
------
One Poisson trace of mixed request classes (fixed prompt length per class,
uniform generation budgets) is drawn up front — open loop, arrivals do not
wait for capacity — and driven through three configurations:

* ``engine_f32``  — :class:`repro.serve.ServeEngine`, f32 paged KV pool.
* ``engine_int8`` — same engine, int8 KV pool (blockwise scales); greedy
  tokens are compared request-by-request against the f32 run (parity).
* ``baseline_static`` — the pre-engine static-batch loop at *equal batch*:
  per class, requests are packed into fixed batches, the prompt runs
  through one prefill, then lockstep decode with **host-side** argmax (the
  device→host→device round trip the engine eliminated).  Every batch runs
  to its longest member, so the padding waste is measured, not modeled.

All throughput numbers are steady-state: each program's first (compiling)
invocation is timed separately and excluded.  Only generated tokens count
toward decode tok/s (prompt tokens go to prefill tok/s); for the baseline,
a request stops counting once its own budget is exhausted even though its
batch keeps stepping — so the reported tok/s is *useful* tokens per second.

Latency is per completed request: TTFT (arrival → first token, queueing
included) and mean per-token latency, reported p50/p99 overall and per
class — the serving analog of the paper's worst-distribution metrics.
The numbers come straight out of the engine's run report
(``report["latency"]``), which derives them from the ``finished`` trace
records the engine emits — one accounting shared with ``launch/serve.py``
and ``python -m repro.obs report`` (:mod:`repro.obs.report`).

Run:  PYTHONPATH=src python benchmarks/bench_serve.py --smoke
      PYTHONPATH=src python benchmarks/bench_serve.py --arch qwen2_0_5b \
          --rate 4 --horizon 30 --out BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models import TransformerLM
from repro.obs import MetricsSink
from repro.serve import (
    ServeEngine,
    TrafficClass,
    merge_prefill_cache,
    poisson_trace,
)
from repro.utils.compile_cache import enable_compile_cache

SMOKE_CLASSES = (
    TrafficClass("chat", prompt_len=6, gen_min=2, gen_max=16, weight=3.0),
    TrafficClass("doc", prompt_len=20, gen_min=2, gen_max=10, weight=1.0),
)
FULL_CLASSES = (
    TrafficClass("chat", prompt_len=32, gen_min=4, gen_max=64, weight=3.0),
    TrafficClass("doc", prompt_len=96, gen_min=4, gen_max=32, weight=1.0),
)


def run_engine(model, params, trace, *, max_batch, max_len, page_size,
               quantized, clock, log_every) -> tuple[dict, dict]:
    """One engine pass; returns (json record, {rid: tokens})."""
    sink = MetricsSink(None)
    engine = ServeEngine(model, params, max_batch=max_batch, max_len=max_len,
                         page_size=page_size, quantized=quantized,
                         sink=sink, log_every=log_every)
    report = engine.run(list(trace), clock=clock)
    occ = [r["kv_occupancy"] for r in sink.records("serve")]
    completions = report["completions"]
    record = {
        "quantized": quantized,
        "steps": report["steps"],
        "wall_s": report["wall_s"],
        "completed": report["completed"],
        "decode_tok_s": report["decode"]["tok_s"],
        "decode_compile_s": report["decode"]["compile_s"],
        "decode_steady_s": report["decode"]["steady_s"],
        "decode_tokens": report["decode"]["steady_tokens"],
        "prefill_tok_s": report["prefill"]["tok_s"],
        "kv_occupancy_mean": float(np.mean(occ)) if occ else 0.0,
        "kv_occupancy_max": float(np.max(occ)) if occ else 0.0,
        # the engine's own accounting, derived from its finished-request
        # trace records — not recomputed here
        "latency": report["latency"],
        "programs": report["programs"],
    }
    tokens = {c.rid: c.tokens for c in completions}
    return record, tokens


def run_static_baseline(model, params, trace, *, max_batch) -> dict:
    """The pre-engine loop: class-batched prefill + lockstep decode with
    host-side argmax, every batch padded to ``max_batch`` and run to its
    longest member.  Steady-state only; useful tokens only."""
    by_class: dict[tuple, list] = {}
    for r in trace:
        by_class.setdefault((r.cls, r.s0), []).append(r)

    steady_s = 0.0
    compile_s = 0.0
    useful_tokens = 0
    lockstep_tokens = 0
    for (cls, s0), rs in sorted(by_class.items()):
        gen_cap = max(r.max_new for r in rs)
        cache_len = s0 + gen_cap
        prefill = jax.jit(model.prefill)
        decode = jax.jit(model.decode_step, donate_argnums=(3,))
        first_of_class = True
        for lo in range(0, len(rs), max_batch):
            chunk = rs[lo:lo + max_batch]
            padded = chunk + [chunk[-1]] * (max_batch - len(chunk))
            prompts = jnp.asarray(np.stack([r.prompt for r in padded]))
            t0 = time.perf_counter()
            logits, pf = prefill(params, {"tokens": prompts})
            cache = merge_prefill_cache(model, pf, max_batch, cache_len, s0)
            jax.block_until_ready(logits)
            dt = time.perf_counter() - t0
            if first_of_class:
                compile_s += dt      # prefill kept out of decode accounting
            steps = max(r.max_new for r in chunk)
            for t in range(steps):
                ts = time.perf_counter()
                # the pre-engine loop: pull logits to the host, argmax
                # there, push the token back — one round trip per step
                tok = np.argmax(np.asarray(logits), axis=-1)
                logits, cache = decode(
                    params, jnp.asarray(tok[:, None], jnp.int32),
                    jnp.int32(s0 + t), cache)
                if t == steps - 1:
                    jax.block_until_ready(logits)
                dt = time.perf_counter() - ts
                useful = sum(1 for r in chunk if r.max_new > t)
                if first_of_class and t == 0:
                    compile_s += dt
                else:
                    steady_s += dt
                    useful_tokens += useful
                    lockstep_tokens += max_batch
            first_of_class = False
    return {
        "decode_tok_s": useful_tokens / steady_s if steady_s else 0.0,
        "lockstep_tok_s": lockstep_tokens / steady_s if steady_s else 0.0,
        "decode_steady_s": steady_s,
        "compile_s": compile_s,
        "useful_tokens": useful_tokens,
        "lockstep_tokens": lockstep_tokens,
        "utilization": (useful_tokens / lockstep_tokens
                        if lockstep_tokens else 0.0),
    }


def _parity(tokens_a: dict, tokens_b: dict) -> dict:
    rids = sorted(set(tokens_a) & set(tokens_b))
    match = sum(1 for rid in rids
                if np.array_equal(tokens_a[rid], tokens_b[rid]))
    return {"requests": len(rids), "matching": match,
            "fraction": match / len(rids) if rids else 1.0}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny deterministic (steps-clock) configuration")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--rate", type=float, default=None,
                    help="arrivals per clock unit (default: smoke 0.8/step, "
                         "full 4/s)")
    ap.add_argument("--horizon", type=float, default=None,
                    help="trace length in clock units (default: smoke 40, "
                         "full 30)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=4)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    enable_compile_cache()

    classes = SMOKE_CLASSES if args.smoke else FULL_CLASSES
    clock = "steps" if args.smoke else "wall"
    rate = args.rate if args.rate is not None else (3.0 if args.smoke else 4.0)
    horizon = args.horizon if args.horizon is not None else \
        (40.0 if args.smoke else 30.0)
    max_batch = min(args.batch, 4) if args.smoke else args.batch
    max_len = max(c.prompt_len + c.gen_max for c in classes)

    cfg = get_arch(args.arch, smoke=args.smoke)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    trace = poisson_trace(classes, rate=rate, horizon=horizon,
                          vocab=cfg.vocab, seed=args.seed)
    print(f"{cfg.name}: {len(trace)} requests, batch={max_batch} "
          f"max_len={max_len} clock={clock}")

    f32_rec, f32_tokens = run_engine(
        model, params, trace, max_batch=max_batch, max_len=max_len,
        page_size=args.page_size, quantized=False, clock=clock,
        log_every=args.log_every)
    int8_rec, int8_tokens = run_engine(
        model, params, trace, max_batch=max_batch, max_len=max_len,
        page_size=args.page_size, quantized=True, clock=clock,
        log_every=args.log_every)
    int8_rec["token_parity_vs_f32"] = _parity(f32_tokens, int8_tokens)
    baseline = run_static_baseline(model, params, trace,
                                   max_batch=max_batch)

    speedup = (f32_rec["decode_tok_s"] / baseline["decode_tok_s"]
               if baseline["decode_tok_s"] else 0.0)
    record = {
        "arch": cfg.name,
        "smoke": args.smoke,
        "max_batch": max_batch,
        "max_len": max_len,
        "page_size": args.page_size,
        "clock": clock,
        "trace": {
            "requests": len(trace),
            "rate": rate,
            "horizon": horizon,
            "classes": {c.name: {"prompt_len": c.prompt_len,
                                 "gen_min": c.gen_min, "gen_max": c.gen_max,
                                 "weight": c.weight} for c in classes},
        },
        "engine_f32": f32_rec,
        "engine_int8": int8_rec,
        "baseline_static": baseline,
        "speedup_vs_static": speedup,
        "meets_1_5x": speedup >= 1.5,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")

    lat = f32_rec["latency"]
    print(f"engine f32:  {f32_rec['decode_tok_s']:8.1f} tok/s  "
          f"ttft p50/p99 {lat['ttft_p50_s']*1e3:.1f}/"
          f"{lat['ttft_p99_s']*1e3:.1f} ms  "
          f"kv_occ mean/max {f32_rec['kv_occupancy_mean']:.2f}/"
          f"{f32_rec['kv_occupancy_max']:.2f}")
    print(f"engine int8: {int8_rec['decode_tok_s']:8.1f} tok/s  "
          f"greedy parity {int8_rec['token_parity_vs_f32']['matching']}/"
          f"{int8_rec['token_parity_vs_f32']['requests']}")
    print(f"baseline:    {baseline['decode_tok_s']:8.1f} useful tok/s  "
          f"(lockstep {baseline['lockstep_tok_s']:.1f}, "
          f"utilization {baseline['utilization']:.2f})")
    print(f"speedup vs static batch: {speedup:.2f}x "
          f"({'meets' if record['meets_1_5x'] else 'BELOW'} 1.5x target)")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
