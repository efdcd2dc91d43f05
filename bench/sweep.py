#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell once, to find its knee: the
highest rate the engine sustains with no growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 4,6,8 [--seconds 20]

One engine, warmed as a benchmark run warms it; for each rate one window of
the cell's traffic at that rate.  Prints, per rate, the completed rate, the
time the backlog took to drain after the last arrival, and the TTFT and
TPOT tails.  A rate is sustained when the drain takes about one request's
length and TTFT does not grow with the window.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    from bench.harness import common, program, serve
    from bench.traffic.gen import serve_requests, warmup_requests
    from bench.weights import make_params

    spec = common.resolve(args.workload, seed=args.seed, seconds=args.seconds,
                          trace=False)
    common.enable_compile_cache()
    common.require_chip(spec)
    cfg, job = spec.cfg, spec.job
    engine = serve.build(spec, program.model(cfg), make_params(cfg, args.seed))
    warm = warmup_requests(job, vocab=cfg["vocab_size"], seed=args.seed,
                           count=job["max_batch"])
    engine.run(serve._requests(warm), clock="steps")
    for rate in (float(x) for x in args.rates.split(",")):
        reqs = serve_requests(dict(job, rate=rate), vocab=cfg["vocab_size"],
                              seed=args.seed, seconds=args.seconds)
        rep = engine.run(serve._requests(reqs), clock="wall")
        comp = rep["completions"]
        arr = {r.rid: r.arrival for r in reqs}
        ttft = [(c.t_first - arr[c.rid]) * 1e3 for c in comp]
        tpot = [c.per_token_s * 1e3 for c in comp if c.n_tokens > 1]
        end = max(c.t_done for c in comp)
        late = [t for c, t in zip(comp, ttft) if arr[c.rid] > args.seconds / 2]
        early = [t for c, t in zip(comp, ttft) if arr[c.rid] <= args.seconds / 2]
        print(json.dumps({
            "rate": rate, "requests": len(reqs), "completed": len(comp),
            "completed_per_s": len(comp) / end, "drain_s": end - args.seconds,
            "output_tokens_per_s": sum(c.n_tokens for c in comp) / end,
            "ttft_p50_ms": common.percentile(ttft, 50),
            "ttft_p95_ms": common.percentile(ttft, 95),
            "ttft_p50_first_half_ms": common.percentile(early, 50),
            "ttft_p50_second_half_ms": common.percentile(late, 50),
            "tpot_p50_ms": common.percentile(tpot, 50),
            "tpot_p95_ms": common.percentile(tpot, 95),
            "steps": rep["steps"],
            "decode_steady_ms": 1e3 * rep["decode"]["steady_s"]
            / max(rep["decode"]["steady_steps"], 1)}), flush=True)


if __name__ == "__main__":
    main()
