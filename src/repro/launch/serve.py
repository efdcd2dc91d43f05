"""Serving CLI: static-batch generation or the continuous-batching engine.

The machinery lives in :mod:`repro.serve` — prompt ingestion and the fused
sample+decode loop in ``repro.serve.prefill`` (re-exported here for
compatibility), the paged-pool engine in ``repro.serve.engine``.  This
module is the thin command-line front:

* default: static-batch :func:`timed_generate` — one prompt batch, fused
  in-jit sampling, and *honest* throughput numbers: compile time and
  steady-state are reported separately, prefill and decode each get their
  own tok/s, and prompt tokens are never counted as generated.
* ``--engine``: drive a :class:`repro.serve.ServeEngine` over an open-loop
  Poisson trace (mixed request classes, paged/int8 KV pool).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_7b --smoke \
      --batch 4 --prompt-len 32 --gen-len 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
      --engine --rate 2.0 --horizon 8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models import TransformerLM
from repro.serve.prefill import (  # noqa: F401  (compat re-exports)
    greedy_generate,
    merge_prefill_cache,
)
from repro.serve.sampling import sample_tokens
from repro.utils.compile_cache import enable_compile_cache


def timed_generate(model: TransformerLM, params, prompt, gen_len: int,
                   temperature: float = 0.0, seed: int = 0,
                   use_prefill: bool = True):
    """:func:`repro.serve.greedy_generate` with phase accounting.

    Returns ``(tokens (B, gen_len), stats)``.  ``stats`` separates what the
    old driver conflated: ``prefill`` vs ``decode`` seconds, and within
    each the first (compiling) invocation vs steady state.  tok/s rates
    divide only the tokens that phase actually processed — prompt tokens
    count toward prefill, generated tokens toward decode.
    """
    cfg = model.cfg
    b, s0 = prompt.shape
    cache_len = s0 + gen_len
    decode = jax.jit(model.decode_step, donate_argnums=(3,))

    def sample_then_decode(params, logits, pos, cache, key, temp):
        key, sub = jax.random.split(key)
        tok = sample_tokens(logits, sub, temp)
        logits, cache = model.decode_step(params, tok[:, None], pos, cache)
        return tok, logits, cache, key

    step = jax.jit(sample_then_decode, donate_argnums=(3,))
    stats = {"prefill": {"compile_s": 0.0, "steady_s": 0.0, "tokens": 0},
             "decode": {"compile_s": 0.0, "steady_s": 0.0, "tokens": 0}}

    if use_prefill and cfg.frontend == "token":
        prefill_fn = jax.jit(model.prefill)
        t0 = time.monotonic()
        logits, pf = prefill_fn(params, {"tokens": prompt})
        jax.block_until_ready(logits)
        t1 = time.monotonic()
        # same shapes -> steady-state program; its outputs are the ones used
        logits, pf = prefill_fn(params, {"tokens": prompt})
        jax.block_until_ready(logits)
        t2 = time.monotonic()
        stats["prefill"] = {"compile_s": max(0.0, (t1 - t0) - (t2 - t1)),
                            "steady_s": t2 - t1, "tokens": b * s0}
        cache = merge_prefill_cache(model, pf, b, cache_len, s0)
    else:
        cache = model.init_cache(b, cache_len)
        logits = None
        t0 = time.monotonic()
        for t in range(s0):
            logits, cache = decode(params, prompt[:, t:t + 1], jnp.int32(t),
                                   cache)
            if t == 0:
                jax.block_until_ready(logits)
                t1 = time.monotonic()
        jax.block_until_ready(logits)
        t2 = time.monotonic()
        stats["prefill"] = {"compile_s": t1 - t0, "steady_s": t2 - t1,
                            "tokens": b * max(0, s0 - 1)}

    key = jax.random.PRNGKey(seed)
    temp = jnp.full((b,), temperature, jnp.float32)
    outs = []
    t0 = time.monotonic()
    t1 = None
    for t in range(gen_len):
        tok, logits, cache, key = step(params, logits, jnp.int32(s0 + t),
                                       cache, key, temp)
        outs.append(tok)
        if t == 0:
            jax.block_until_ready(tok)
            t1 = time.monotonic()
    out = jnp.stack(outs, axis=1)
    jax.block_until_ready(out)
    t2 = time.monotonic()
    stats["decode"] = {"compile_s": (t1 - t0) if t1 is not None else 0.0,
                       "steady_s": (t2 - t1) if t1 is not None else 0.0,
                       "tokens": b * max(0, gen_len - 1)}
    for ph in stats.values():
        ph["tok_s"] = ph["tokens"] / ph["steady_s"] if ph["steady_s"] else 0.0
    return out, stats


def _run_static(args, model, params, cfg) -> None:
    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    out, stats = timed_generate(model, params, prompt, args.gen_len,
                                args.temperature, args.seed,
                                use_prefill=not args.no_prefill)
    pf, dc = stats["prefill"], stats["decode"]
    print(f"generated {out.shape}")
    print(f"prefill: {pf['tokens']} prompt tok, compile {pf['compile_s']:.2f}s,"
          f" steady {pf['steady_s']:.3f}s -> {pf['tok_s']:.1f} tok/s")
    print(f"decode:  {dc['tokens']} new tok,    compile {dc['compile_s']:.2f}s,"
          f" steady {dc['steady_s']:.3f}s -> {dc['tok_s']:.1f} tok/s")
    print("sample:", np.asarray(out[0][:16]))


def _run_engine(args, model, params, cfg) -> None:
    from repro.obs import MetricsSink
    from repro.serve import SMOKE_CLASSES, ServeEngine, poisson_trace

    # context bound from the traffic classes' worst case, not --prompt-len
    max_len = max(c.prompt_len + c.gen_max for c in SMOKE_CLASSES)
    engine = ServeEngine(
        model, params, max_batch=args.batch, max_len=max_len,
        page_size=args.page_size, quantized=args.int8_kv, seed=args.seed,
        sink=MetricsSink(args.log_dir) if args.log_dir else None,
        log_every=args.log_every)
    trace = poisson_trace(SMOKE_CLASSES, rate=args.rate,
                          horizon=args.horizon, vocab=cfg.vocab,
                          seed=args.seed)
    report = engine.run(trace, clock="steps" if args.smoke else "wall")
    dc = report["decode"]
    print(f"engine: {report['completed']}/{report['admitted']} requests, "
          f"{report['steps']} steps in {report['wall_s']:.2f}s")
    print(f"decode: compile {dc['compile_s']:.2f}s, steady "
          f"{dc['steady_s']:.3f}s -> {dc['tok_s']:.1f} tok/s "
          f"({dc['steady_tokens']} tok)")
    # latency comes from the engine's finished trace records — the same
    # accounting bench_serve and `python -m repro.obs report` use
    lat = report["latency"]
    if lat["requests"]:
        line = (f"latency: ttft p50 {lat['ttft_p50_s']:.3f}s "
                f"p99 {lat['ttft_p99_s']:.3f}s")
        if "per_token_p50_s" in lat:
            line += (f", per-token p50 {lat['per_token_p50_s'] * 1e3:.1f}ms "
                     f"p99 {lat['per_token_p99_s'] * 1e3:.1f}ms")
        print(line)
        for cls, d in lat["per_class"].items():
            print(f"  class {cls}: {d['requests']} req, "
                  f"ttft p50 {d['ttft_p50_s']:.3f}s p99 {d['ttft_p99_s']:.3f}s")
    print(f"programs: {report['programs']}")
    engine.sink.close()
    if engine.sink.path:
        print(f"telemetry: {engine.sink.path}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-prefill", action="store_true",
                    help="force the token-by-token decode-path prompt loop")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine over a Poisson trace")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="engine: arrivals per clock unit")
    ap.add_argument("--horizon", type=float, default=16.0,
                    help="engine: trace length in clock units")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--log-dir", default=None)
    ap.add_argument("--log-every", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch, smoke=args.smoke)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    print(f"serving {cfg.name}: {model.num_params():,} params, "
          f"batch={args.batch} engine={args.engine}")
    if args.engine:
        _run_engine(args, model, params, cfg)
    else:
        _run_static(args, model, params, cfg)


if __name__ == "__main__":
    main()
