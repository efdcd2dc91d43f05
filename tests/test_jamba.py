"""Jamba (``configs/jamba2_3b.py``, the benchmark's ``jamba2-3b``) through
the program's normal path, against the plain reference.

* The benchmark's family (``bench/families/jamba.py``) lays out exactly the
  parameters ``TransformerLM`` declares at the published widths, and its
  period holds 1,598,556,096 of them.
* At a small size on the CPU, with seeded random weights, the program's
  full-sequence logits and the serving engine's prefill-then-decode logits
  agree with ``bench/reference/jamba.py``; the same comparison fails with
  the Mamba mixers' inner norms left out, and with RoPE put in.
* The Mamba layers' ``obs:ssm/conv`` and ``obs:ssm/scan`` scopes are in the
  engine's prefill and decode programs, and each admission span counts the
  tokens its prefill scans (``scan_tokens``) and those of them that went
  through the ``mamba_scan`` kernel (``scan_kernel_tokens``).
* A tree given to an engine that narrows its weights is held narrowed, and
  the admissions' host reader the cell is listed in reads a Jamba run.
* The family's scan and decode costs equal numbers worked by hand, and the
  benchmark's ``ssm_scan_*`` readers read the prefill's scan ops and the
  admissions' ``scan_tokens`` over the admissions the trace holds, in the
  cell they list alone.
"""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_arch
from repro.kernels.mamba_scan import ops as mamba_scan_ops
from repro.models import TransformerLM, ssm
from repro.serve import Request, ServeEngine
from repro.serve import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import families  # noqa: E402
from bench.weights import flatten, make_params  # noqa: E402
from test_obs_spans import _cell_ctx, _reader  # noqa: E402

with open(os.path.join(ROOT, "bench", "configs", "jamba2-3b.json")) as _f:
    CFG = json.load(_f)

#: the configuration at a CPU test's size: every key of the published one,
#: two periods of 4 layers (attention third), so the layer scan runs
SMALL = dict(CFG, name="jamba-small", hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=1, vocab_size=256,
             mamba_dt_rank=8, mamba_d_state=4, attn_layer_period=4,
             attn_layer_offset=2, num_hidden_layers=8)

#: float32 on the CPU: the program and the reference add the same terms in
#: other orders (chunked against whole softmax, the scan's read-out, the
#: layer scan), which over 8 layers moves logits of size ~4 by a few 1e-5;
#: a dropped norm or an added rotation moves them by whole units
TOL = 2e-4


def _model(cfg=SMALL, **change):
    arch = families.load(cfg).arch_config(cfg)
    return TransformerLM(dataclasses.replace(arch, **change))


def _reference_logits(params, toks, positions):
    ref = families.load(SMALL).reference()
    return np.asarray(ref.logits_at(SMALL, params, jnp.asarray(toks),
                                    jnp.asarray(positions)))


def _close(got, want) -> bool:
    return float(np.max(np.abs(got - want))) <= TOL * float(np.max(np.abs(want)))


# -- the layout ------------------------------------------------------------------

def test_family_lays_out_the_programs_parameters_at_published_widths():
    fam = families.load(CFG)
    model = TransformerLM(fam.arch_config(CFG))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert {k: tuple(v.shape) for k, v in flatten(shapes).items()} == \
        {k: tuple(v) for k, v in fam.shapes(CFG).items()}
    assert families.param_count(CFG) == model.num_params() == 1_598_556_096
    assert fam.mamba_layers(CFG) == 13 and fam.attention_layers(CFG) == 1
    # the whole published model is the registry's jamba2-3b
    whole = dict(CFG, num_hidden_layers=28)
    assert dataclasses.replace(fam.arch_config(whole), name="jamba2-3b") == \
        get_arch("jamba2-3b")


# -- against the reference -------------------------------------------------------

def test_full_sequence_logits_match_the_reference():
    params = make_params(SMALL, 5)
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    got = np.asarray(_model().logits_all(params, {"tokens": jnp.asarray(toks[None])}))[0]
    assert _close(got, _reference_logits(params, toks, np.arange(40)))


def _engine_logits(model, params, prompts, steps):
    """Each prompt admitted to a slot of one engine, then ``steps`` decode
    steps of every slot together, teacher-forced with the given tokens:
    the logits (steps, slots, V) and the full token rows."""
    rng = np.random.default_rng(1)
    engine = ServeEngine(model, params, max_batch=len(prompts), max_len=64,
                         page_size=4)
    for rid, prompt in enumerate(prompts):
        engine.sched.submit(Request(rid=rid, prompt=prompt, max_new=steps + 1,
                                    arrival=0.0))
        engine._admit(engine.sched.next_admission(), 0.0)
    rows = [list(p) for p in prompts]
    decode = jax.jit(model.paged_decode_step, static_argnames=("max_len",))
    c = engine._carry
    tok, pos, cache = c["tok"], c["pos"], c["cache"]
    out = []
    for _ in range(steps):
        logits, cache = decode(engine.params, tok, pos, cache, engine._tables,
                               max_len=64)
        out.append(np.asarray(logits))
        nxt = rng.integers(0, model.cfg.vocab, (len(prompts),)).astype(np.int32)
        for row, t in zip(rows, nxt):
            row.append(int(t))
        tok, pos = jnp.asarray(nxt)[:, None], pos + 1
    return np.stack(out), rows


def test_engine_prefill_then_decode_matches_the_reference():
    params = make_params(SMALL, 7)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (13, 29)]
    steps = 6
    got, rows = _engine_logits(_model(), params, prompts, steps)
    for slot, (prompt, row) in enumerate(zip(prompts, rows)):
        # position s0 - 1 + t predicts the token after it
        positions = len(prompt) - 1 + np.arange(steps)
        want = _reference_logits(params, np.asarray(row[:-1], np.int32), positions)
        assert _close(got[:, slot], want), slot


def test_the_benchmarks_serve_run_is_correct_on_the_cpu():
    """The cell's whole path (layout check, seeded weights, engine, check
    against the reference) at a small size: every request served, and the
    served tokens the reference's best."""
    from bench.harness import serve
    from bench.tests import smoke

    job = dict(smoke.SERVE_JOB, max_len=48)
    spec = smoke.spec(SMALL, job, {"served_mean_gap": 0.0004})
    result, checks = serve.run(spec, smoke.devices(), 0.0)
    assert result["correct"] and result["failed"] == 0, checks
    assert checks["served_mean_gap"]["value"] < 1e-5


@pytest.mark.parametrize("change", [None, {"rope_theta": 10000.0}],
                         ids=["no_inner_norms", "rope"])
def test_the_comparison_fails_without_jamba(change, monkeypatch):
    """The fault ``None`` is a Mamba mixer that skips its inner norms."""
    if change is None:
        monkeypatch.setattr(ssm, "rmsnorm", lambda p, x, eps: x)
    params = make_params(SMALL, 5)
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    got = np.asarray(_model(**(change or {})).logits_all(
        params, {"tokens": jnp.asarray(toks[None])}))[0]
    assert not _close(got, _reference_logits(params, toks, np.arange(40)))


# -- scopes and counters -----------------------------------------------------------

def _op_names(lowered) -> set:
    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


@pytest.mark.parametrize("path,groups", [("ref", 1), ("kernel", 1), ("kernel", 2)],
                         ids=["ref", "kernel", "kernel_scanned_groups"])
def test_scopes_in_the_prefill_and_decode_programs_and_scan_tokens_on_admits(
        path, groups, monkeypatch):
    """On the kernel path (the ``mamba_scan`` kernel in interpret mode) every
    scanned token is a kernel token, also where a ``lax.scan`` over the
    layer groups traces one group's layers for all; on the reference path,
    the CPU's, none."""
    if path == "kernel":
        monkeypatch.setattr(mamba_scan_ops, "kernel_path",
                            lambda use_kernel, interpret: "interpret")
    cfg = get_arch("jamba2_3b", smoke=True)
    cfg = dataclasses.replace(cfg, n_layers=cfg.n_layers * groups)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=2, max_len=24, page_size=4)
    prompts = {0: 9, 1: 1, 2: 5}
    obs.clear_spans()
    engine.run([Request(rid=r, prompt=np.arange(n, dtype=np.int32) % model.cfg.vocab,
                        max_new=3, arrival=0.0) for r, n in prompts.items()],
               clock="steps")
    admits = [s for s in obs.spans() if s.name == "obs:serve/admit"]
    mamba = sum(b == "mamba" for b, _ in model.cfg.group_pattern()) * model.cfg.n_groups
    assert (mamba, model.cfg.n_groups) == (3 * groups, groups)
    assert {s.attrs["rid"]: s.attrs["scan_tokens"] for s in admits} == \
        {r: (n - 1) * mamba for r, n in prompts.items()}
    assert {s.attrs["rid"]: s.attrs["scan_kernel_tokens"] for s in admits} == \
        {r: (n - 1) * mamba * (path == "kernel") for r, n in prompts.items()}

    prefill = _op_names(engine._admit_fns[9].lower(
        engine.params, jnp.zeros((1, 8), jnp.int32), engine._carry["cache"],
        {"attn": jnp.zeros((6,), jnp.int32)}, jnp.int32(0)))
    step = _op_names(engine._step_fn.lower(engine.params, engine._carry,
                                           engine._tables))
    for names, outer in ((prefill, "obs:serve/prefill"), (step, "obs:serve/decode")):
        for scope in ("obs:ssm/conv", "obs:ssm/scan"):
            assert any(outer in n and scope in n for n in names), (outer, scope)


def test_dense_admissions_scan_nothing():
    model = TransformerLM(get_arch("qwen2_0_5b", smoke=True))
    engine = ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_batch=2,
                         max_len=24, page_size=4)
    obs.clear_spans()
    engine.run([Request(rid=0, prompt=np.arange(7, dtype=np.int32), max_new=2,
                        arrival=0.0)], clock="steps")
    admits = [s for s in obs.spans() if s.name == "obs:serve/admit"]
    assert [s.attrs["scan_tokens"] for s in admits] == [0]
    assert [s.attrs["scan_kernel_tokens"] for s in admits] == [0]


def test_the_admission_host_reader_reads_a_jamba_run_in_the_cell():
    """``admit_host_ms.tput`` lists the cell, so it must read a Jamba
    engine's admissions there: each has its ``obs:serve/admit_wait``."""
    model = TransformerLM(get_arch("jamba2_3b", smoke=True))
    engine = ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_batch=2,
                         max_len=24, page_size=4)
    obs.clear_spans()
    engine.run([Request(rid=r, prompt=np.arange(n, dtype=np.int32), max_new=3,
                        arrival=0.0) for r, n in enumerate((9, 5, 1))],
               clock="steps")
    ctx = _cell_ctx("serve.jamba2-3b.longdoc", {})
    assert _reader("admit_host_ms.tput").read(ctx) > 0


def test_a_tree_given_to_a_narrowing_engine_is_held_narrowed(monkeypatch):
    """``engine.params = tree`` keeps the dtypes the engine compiled for."""
    monkeypatch.setattr(engine_mod, "narrows_weights",
                        lambda p: bool(jax.tree.leaves(p)))
    model = TransformerLM(get_arch("jamba2_3b", smoke=True))
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=2, max_len=24, page_size=4)
    want = jax.tree.map(lambda x: x.dtype, engine.params)
    engine.params = model.init(jax.random.PRNGKey(1))
    assert jax.tree.map(lambda x: x.dtype, engine.params) == want
    assert engine.params["lm_head"]["table"].dtype == jnp.bfloat16
    engine.params = None
    assert engine.params is None


# -- costs ------------------------------------------------------------------------

TOY = dict(SMALL, hidden_size=8, intermediate_size=16, num_attention_heads=2,
           num_key_value_heads=1, vocab_size=10, mamba_expand=2, mamba_dt_rank=2,
           mamba_d_state=3, mamba_d_conv=4, attn_layer_period=4,
           attn_layer_offset=2, num_hidden_layers=4)


def test_costs_worked_by_hand():
    fam = families.load(TOY)
    # d_inner 16, head_dim 4; 3 Mamba layers, 1 attention layer
    # scan of 5 tokens: 7 * 16 * 3 * 5 * 3 operations; bytes
    # ((3 * 16 + 2 * 3) * 4 * 5 + 2 * 4 * 16 * 3) * 3
    assert fam.scan_cost(TOY, 5) == (5040.0, 4392.0)
    # matmul params: attention 8*2*4 + 2*8*1*4 + 2*4*8 = 192; Mamba
    # 8*32 + 16*(2+6) + 2*16 + 16*8 = 544 (x3); MLPs 3*8*16 (x4); head 80
    assert fam.matmul_params(TOY) == 192 + 3 * 544 + 4 * 384 + 80 == 3440
    # parameters: the matmuls, 4 layers' two norms (64), the final norm (8),
    # each Mamba layer's conv (64 + 16), dt_bias, d_skip (16 + 16), a_log
    # (48) and dt/B/C norms (2 + 3 + 3)
    assert families.param_count(TOY) == 3440 + 64 + 8 + 3 * 168 == 4016
    # one decode step of 2 requests over 9 context tokens: operations
    # 2 * 3440 * 2 + 4 * 2 * 4 * 9 + 2 * 3 * (7 * 16 * 3 + 2 * 16 * 4);
    # bytes 4016 * 4 + (2 * 1 * 4 * 4) * 9 + 2 * 2 * 3 * (48 + 48) * 4
    assert fam.decode_step_cost(TOY, 2, 9) == (
        13760 + 288 + 2784, 16064 + 288 + 4608)
    assert fam.kv_bytes_per_token(TOY) == 32


# -- the benchmark's scan readers ----------------------------------------------------

class _Trace:
    """One chip's device ops, (start, ns, name scope path, category), and
    programs, (start, ns, name)."""

    def __init__(self, ops, modules):
        self._ops, self._modules = ops, modules

    def ops(self, dev, window):
        start, dur, tf_op, category = zip(*self._ops)
        return {"start": np.asarray(start), "dur": np.asarray(dur),
                "end": np.asarray(start) + np.asarray(dur),
                "tf_op": list(tf_op), "category": list(category)}

    def modules(self, dev, window):
        start, dur, name = zip(*self._modules)
        return {"start": np.asarray(start), "dur": np.asarray(dur),
                "end": np.asarray(start) + np.asarray(dur), "name": list(name)}


SCAN = "jit(admit)/obs:serve/prefill/obs:ssm/scan/while/body/mul"
#: two prefills (0-10 ms and 20-30 ms) and a decode step between them
OPS = [(0.0, 2e6, SCAN, "loop fusion"),
       (2e6, 1e6, SCAN, "data formatting"),
       (0.0, 5e6, "jit(admit)/obs:serve/prefill/while", "while"),   # a container
       (3e6, 7e6, "jit(admit)/obs:serve/prefill/obs:ssm/conv/add", "loop fusion"),
       (12e6, 4e6, "jit(step)/obs:serve/decode/obs:ssm/scan/mul", "loop fusion"),
       (20e6, 6e6, SCAN, "loop fusion")]
MODULES = [(0.0, 10e6, "jit_admit(7)"), (12e6, 5e6, "jit_step(9)"),
           (20e6, 10e6, "jit_admit(7)")]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _scan_ctx(cell, ops=OPS, modules=MODULES):
    ctx = _cell_ctx(cell, {"admitted": 3})
    ctx.trace, ctx.devices, ctx.window = _Trace(ops, modules), [0], (0, 1)
    ctx.peaks = PEAKS
    return ctx


def _admissions(prompts, **counters):
    obs.clear_spans()
    with obs.host_scope("obs:serve/run"):
        for rid, tokens in enumerate(prompts):
            with obs.host_scope("obs:serve/admit", rid=rid, prompt_tokens=tokens,
                                **{k: f(tokens) for k, f in counters.items()}):
                pass


def test_scan_readers_read_the_prefills_scan_and_its_roofline():
    # three admissions, the second without a prompt (no prefill program)
    _admissions((4096, 0, 2048), scan_tokens=lambda n: 13 * n)
    ctx = _scan_ctx("serve.jamba2-3b.longdoc")
    # 9 ms of scan ops in the two prefills the trace holds
    assert _reader("ssm_scan_ms.tput").read(ctx) == pytest.approx(4.5)
    fam = families.load(CFG)
    least = sum(max(o / 197e12, b / 819e9)
                for o, b in (fam.scan_cost(CFG, 4096), fam.scan_cost(CFG, 2048)))
    roofline = _reader("ssm_scan_roofline.tput")
    assert roofline.read(ctx) == pytest.approx(100.0 * least / 9e-3)
    # bytes bound it: (3 * 5120 + 2 * 16) * 4 bytes a token and layer
    assert least == pytest.approx(13 * (6144 * 61568 + 4 * 4 * 5120 * 16) / 819e9)

    # a trace the profiler cut during the second prefill holds one
    # admission, and the part of the scan that ran before the cut is left out
    cut = _scan_ctx("serve.jamba2-3b.longdoc", OPS[:5] + [(20e6, 3e6, SCAN, "loop fusion")],
                    MODULES[:2])
    assert _reader("ssm_scan_ms.tput").read(cut) == pytest.approx(3.0)
    ops, nbytes = fam.scan_cost(CFG, 4096)
    first = max(ops / 197e12, nbytes / 819e9)
    assert roofline.read(cut) == pytest.approx(100.0 * first / 3e-3)

    # read only in the cells they list
    chat = _scan_ctx("serve.h2o-danube-1.8b.chat")
    assert _reader("ssm_scan_ms.tput").read(chat) is None
    assert roofline.read(chat) is None
    # a program without the scope, or without the counter, gives nothing
    unscoped = [(a, t, "jit(admit)/obs:serve/prefill/while/body/mul", c)
                for a, t, _, c in OPS]
    assert _reader("ssm_scan_ms.tput").read(
        _scan_ctx("serve.jamba2-3b.longdoc", unscoped)) is None
    _admissions((4096, 0, 2048))
    assert roofline.read(ctx) is None
