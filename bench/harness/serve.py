"""The general runner of a serving mix (``kind: serve``).

Set-up makes the seed's weights, builds one ``ServeEngine`` and warms it with
one two-token request per slot, cycling through the mix's prompt lengths:
that compiles the decode step and one prefill program per prompt length,
and touches every slot.  The window is one ``ServeEngine.run(clock="wall")``
over the open-loop trace of ``--seconds`` (arrivals stop there and the
backlog drains); latencies count from each request's scheduled arrival.

``correct`` takes a sample of the finished requests, drawn from the seed and
holding the longest, and runs the plain reference once over each prompt and
its served tokens: the mean, over the served tokens, of the gap by which a
served token's logit lies below the reference's best logit at its position.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, program
from bench.traffic.gen import seed_rng, serve_requests, warmup_requests
from bench.weights import make_params


def _requests(reqs):
    from repro.serve.scheduler import Request

    return [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                    arrival=r.arrival, cls=r.cls) for r in reqs]


def build(spec: common.Spec, model, params):
    from repro.serve import ServeEngine

    job = spec.job
    return ServeEngine(model, params, max_batch=job["max_batch"],
                       max_len=job["max_len"], page_size=job["page_size"],
                       quantized=job["kv_pool"] == "int8",
                       seed=program.program_seed(spec.seed))


def _instrument(engine, records: list):
    """Host spans around the engine's calls into each layer, and each decode
    step's active requests and live context (traced runs only)."""
    decode_once, admit = engine._decode_once, engine._admit

    def traced_decode(*a, **kw):
        active = np.nonzero(engine._active_np)[0]
        kv = sum(engine._slot_meta[s]["req"].s0 + len(engine._slot_tokens[s])
                 for s in active)
        records.append((len(active), kv))
        with common.span("bench:decode_step"):
            return decode_once(*a, **kw)

    def traced_admit(*a, **kw):
        with common.span("bench:admit"):
            return admit(*a, **kw)

    engine._decode_once, engine._admit = traced_decode, traced_admit


def run(spec: common.Spec, devs, t_start: float, hooks=None):
    cfg, job = spec.cfg, spec.job
    hooks = hooks or {}
    phases = {"start": common.now() - t_start}
    model = program.model(cfg)
    # left uncommitted, as the engine's own carry is: a committed argument
    # would commit the carry after the first step and compile a second program
    params = make_params(cfg, spec.seed)
    engine = build(spec, model, params)
    jax.block_until_ready(params)
    phases["weights_engine"] = common.now() - t_start
    if "engine" in hooks:
        hooks["engine"](engine)
    vocab = cfg["vocab_size"]
    warm = warmup_requests(job, vocab=vocab, seed=spec.seed,
                           count=job["max_batch"])
    engine.run(_requests(warm), clock="steps")
    phases["warmup"] = common.now() - t_start
    reqs = serve_requests(job, vocab=vocab, seed=spec.seed,
                          seconds=spec.seconds)
    records: list = []
    if spec.trace:
        _instrument(engine, records)
    setup_s = common.now() - t_start
    before = engine.watchdog.snapshot()

    t_window_ns = time.perf_counter_ns()
    with common.traced(spec.trace) as tr:
        rep = engine.run(_requests(reqs), clock="wall")
    # programs the engine's watchdog saw compiled inside the window
    in_window = sum(n - before.get(k, 0) for k, n in rep["programs"].items())
    trace_s = common.now() - t_start - setup_s - rep["wall_s"]
    device = common.device_record(devs)
    done = {c.rid: c for c in rep["completions"]}
    by_rid = {r.rid: r for r in reqs}
    failed = [r.rid for r in reqs
              if r.rid not in done or done[r.rid].n_tokens != r.max_new]
    ok = [done[r.rid] for r in reqs if r.rid in done]
    ttft = [(c.t_first - by_rid[c.rid].arrival) * 1e3 for c in ok]
    tpot = [c.per_token_s * 1e3 for c in ok if c.n_tokens > 1]
    # a failed request counts as missing every limit
    ttft += [float("inf")] * len(failed)
    tpot += [float("inf")] * len(failed)
    end = max(c.t_done for c in ok) if ok else float("nan")
    out_tok = sum(c.n_tokens for c in ok)
    served = {c.rid: np.asarray(c.tokens) for c in ok}
    del engine, params, rep
    gc.collect()

    t_check = common.now()
    gap = reference_gaps(spec, reqs, served, devs[0])
    check_s = common.now() - t_check
    checks = {name: common.check(gap[name], lim)
              for name, lim in spec.limits.items()}
    correct = not failed and all(c["value"] <= c["limit"]
                                 for c in checks.values())
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": len(failed), "device": device}
    names = {m["name"] for m in spec.end_to_end}
    ttft_p95 = common.percentile(ttft, 95)
    if not spec.trace:
        m = {"serve_output_tokens_per_s": {"value": out_tok / end,
                                           "unit": "tokens/s"},
             "ttft_p95_ms": {"value": ttft_p95, "unit": "ms"},
             "tpot_p95_ms": {"value": common.percentile(tpot, 95), "unit": "ms"},
             "setup_s": {"value": setup_s, "unit": "s"}}
        result["metrics"] = {k: v for k, v in m.items() if k in names}
    else:
        window = tr.trace.window()
        admitted = sum(1 for r in reqs if r.rid in done)
        ctx = common.Context(spec=spec, trace=tr.trace, devices=[d.id for d in devs],
                      window=window, window_s=end,
                      counts={"requests": len(reqs), "admitted": admitted,
                              "decode_steps": records},
                      host={"tpot_ms": tpot, "ttft_ms": ttft})
        result["metrics"] = common.read_per_layer(spec, ctx)
        busy, win = common.busy_and_window(ctx)
        result["device"].update(busy_s=busy, window_s=win)
        result["breakdown"] = common.breakdown(ctx)
    result["log"] = {"setup_s": setup_s, "setup_phases_s": phases,
                     "drain_end_s": end, "trace_s": trace_s,
                     "requests": len(reqs), "output_tokens": out_tok,
                     "compiles_in_window": in_window,
                     "ttft_p50_ms": common.percentile(ttft, 50),
                     "ttft_p95_ms": ttft_p95,
                     "tpot_p50_ms": common.percentile(tpot, 50),
                     **_readback_waits(t_window_ns),
                     "checked_requests": gap["checked"],
                     "checked_tokens": gap["tokens"], "check_s": check_s}
    return result, checks


def _readback_waits(t_window_ns: int) -> dict:
    """The window's decode readbacks (``obs:serve/readback`` spans) that
    waited 100 ms or more, against some 36 a step: where the host stood
    still, every running request and every queued first token waited."""
    from repro.obs import spans

    waits = [(s.end_ns - s.start_ns) / 1e6 for s in spans()
             if s.name == "obs:serve/readback" and s.start_ns >= t_window_ns]
    return {"readbacks_over_100ms": sum(w >= 100 for w in waits),
            "readback_max_ms": max(waits, default=0.0)}


# -- the check --------------------------------------------------------------------

def sample_checked(spec: common.Spec, reqs, served) -> list:
    """The requests the reference checks: drawn from the seed, with the
    longest finished one among them."""
    fin = [r for r in reqs if r.rid in served and len(served[r.rid])]
    if not fin:
        return []
    longest = max(fin, key=lambda r: (len(r.prompt) + len(served[r.rid]), -r.rid))
    rest = [r for r in fin if r.rid != longest.rid]
    n = min(spec.job["check_requests"] - 1, len(rest))
    pick = seed_rng(spec.seed, 5).choice(len(rest), n, replace=False) if n else []
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_logits_fn(cfg, dtype):
    from bench import families

    ref = families.load(cfg).reference()

    def fn(params, toks, pos):
        return ref.logits_at(cfg, params, toks, pos, dtype)

    return jax.jit(fn)


def _gaps(ref, tokens) -> np.ndarray:
    """Per position: the reference's best logit less that of ``tokens``."""
    return ref.max(axis=1) - ref[np.arange(len(tokens)), tokens]


def reference_gaps(spec, reqs, served, device, readings: bool = False,
                   control: bool = False):
    """How far the served tokens lie below the reference's best, over the
    checked requests' served tokens: the mean gap (``served_mean_gap``, the
    number compared), against the reference in float32 at the matmul
    precision the configuration states (``default``: one bfloat16 pass per
    product, as the program runs).

    For calibration only (``bench/calibrate.py``; the benchmark's runs do not
    ask for them): ``readings`` adds the widest gap (``served_gap``), the
    share of tokens off the reference's argmax (``served_mismatch``) and the
    same three against the reference at ``highest`` (suffix ``_highest``);
    ``control`` adds the numbers of the tokens that the bfloat16 reference
    puts first at the same positions, prefixed ``control_``."""
    cfg, job = spec.cfg, spec.job
    checked = sample_checked(spec, reqs, served)
    length = job["max_len"]
    gmax = max(c["gen_max"] for c in job["classes"])
    stated = cfg.get("matmul_precision", "default")
    gaps: dict[str, list] = {}
    params = make_params(cfg, spec.seed, device=device)
    with jax.default_matmul_precision(stated):
        f32 = reference_logits_fn(cfg, jnp.float32)
    if readings:
        with jax.default_matmul_precision("highest"):
            f32_hi = reference_logits_fn(cfg, jnp.float32)
    if control:
        low = jax.jit(lambda p: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), p))(params)
        bf16 = reference_logits_fn(cfg, jnp.bfloat16)
    for r in checked:
        gen = served[r.rid]
        seq = np.concatenate([r.prompt, gen[:-1]]).astype(np.int32)
        toks = np.zeros(length, np.int32)
        toks[:len(seq)] = seq
        pos = np.zeros(gmax, np.int32)
        n = len(gen)
        pos[:n] = len(r.prompt) - 1 + np.arange(n)
        toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        with jax.default_matmul_precision(stated):
            lg = np.asarray(f32(params, toks, pos))[:n]
        hi = None
        if readings:
            with jax.default_matmul_precision("highest"):
                hi = np.asarray(f32_hi(params, toks, pos))[:n]
        picks = {"served": gen}
        if control:
            with jax.default_matmul_precision("default"):
                picks["control"] = np.asarray(bf16(low, toks, pos))[:n].argmax(axis=1)
        for who, tok in picks.items():
            gaps.setdefault(who, []).append(_gaps(lg, tok))
            if hi is not None:
                gaps.setdefault(who + "_highest", []).append(_gaps(hi, tok))
    out = {"checked": len(checked),
           "tokens": int(sum(len(g) for g in gaps.get("served", [])))}
    for key, parts in gaps.items():
        g = np.concatenate(parts)
        who, _, suffix = key.partition("_")
        suffix = "_" + suffix if suffix else ""
        out[f"{who}_mean_gap{suffix}"] = float(g.mean())
        if readings:
            out[f"{who}_gap{suffix}"] = float(g.max())
            out[f"{who}_mismatch{suffix}"] = float(np.mean(g > 0))
    return out
