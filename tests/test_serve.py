"""Serving stack: prefill scatter, paged KV, and the continuous engine.

Layers under test, bottom up:

* ``repro.serve.prefill.greedy_generate(use_prefill=True)`` runs the prompt
  through one compiled ``model.prefill`` and scatters the per-layer caches
  into the decode cache; the old O(S0)-dispatch loop is the reference.
  Both paths must produce identical greedy tokens — including
  sliding-window ring buffers (prompt longer than / exactly at the window)
  and recurrent (mamba/rwkv) states.
* ``model.paged_decode_step`` against a paged pool must be *bit-equal* to
  ``model.decode_step`` against the contiguous cache — same math, only the
  storage layout differs.
* ``repro.serve.ServeEngine``: continuous batching over staggered arrivals
  and slot reuse must reproduce per-request batch-1 ``greedy_generate``
  tokens exactly, from ONE compiled decode program (watchdog-asserted),
  with int8 KV parity on short generations; the step's jaxpr carries no
  stray host callbacks and its lowering is operand-independent.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import audit_host_callbacks, audit_recompile
from repro.configs import get_arch
from repro.launch.serve import greedy_generate, merge_prefill_cache
from repro.models import TransformerLM
from repro.models.attention import paged_kv_len
from repro.serve import (
    PageAllocator,
    Request,
    Scheduler,
    ServeEngine,
    TRASH_PAGE,
    pages_needed,
)
from repro.serve.engine import make_step

# arch choices cover: pure attention, swa ring buffer (prompt 24 > window
# 16), rwkv and mamba/attn hybrid recurrent-state passthrough
CASES = [("qwen2_0_5b", 12), ("gemma2_27b", 24), ("rwkv6_7b", 12),
         ("jamba_1_5_large_398b", 12)]


@pytest.mark.parametrize("arch,prompt_len", CASES)
def test_prefill_generates_identical_tokens(arch, prompt_len):
    cfg = get_arch(arch, smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (2, prompt_len)),
                         jnp.int32)
    fast = greedy_generate(model, params, prompt, 6, use_prefill=True)
    ref = greedy_generate(model, params, prompt, 6, use_prefill=False)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(ref))


def test_merged_cache_matches_decode_built_cache():
    """The scattered prefill cache equals the cache the decode loop builds
    (same slots, same values up to the attention paths' shared projections)."""
    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    b, s0, gen = 2, 10, 4
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (b, s0)), jnp.int32)

    _, pf = jax.jit(model.prefill)(params, {"tokens": prompt})
    merged = merge_prefill_cache(model, pf, b, s0 + gen, s0)

    decode = jax.jit(model.decode_step)
    cache = model.init_cache(b, s0 + gen)
    for t in range(s0):
        _, cache = decode(params, prompt[:, t:t + 1], jnp.int32(t), cache)

    for a, c in zip(jax.tree.leaves(merged), jax.tree.leaves(cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32),
                                   rtol=2e-2, atol=2e-2)


# -- prefill-scatter edge cases ------------------------------------------------

@pytest.mark.parametrize("batch,prompt_len", [
    (1, 16),    # batch 1, prompt length EXACTLY the sliding window (16):
                # the ring scatter must place all window slots with no wrap
    (2, 16),
    (1, 17),    # one past the window: first ring slot already overwritten
])
def test_prefill_at_window_boundary(batch, prompt_len):
    cfg = get_arch("gemma2_27b", smoke=True)     # swa window 16 + full attn
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)),
                         jnp.int32)
    fast = greedy_generate(model, params, prompt, 5, use_prefill=True)
    ref = greedy_generate(model, params, prompt, 5, use_prefill=False)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(ref))


def test_merged_cache_grouped_and_head_layers():
    """Scatter covers both cache shapes: per-layer head entries and the
    (n_groups,)-stacked group entries (jamba: mamba rows + attn KV)."""
    cfg = get_arch("jamba_1_5_large_398b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    b, s0, gen = 1, 8, 4
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (b, s0)), jnp.int32)

    _, pf = jax.jit(model.prefill)(params, {"tokens": prompt})
    merged = merge_prefill_cache(model, pf, b, s0 + gen, s0)

    decode = jax.jit(model.decode_step)
    cache = model.init_cache(b, s0 + gen)
    for t in range(s0):
        _, cache = decode(params, prompt[:, t:t + 1], jnp.int32(t), cache)

    leaves_m, leaves_c = jax.tree.leaves(merged), jax.tree.leaves(cache)
    assert len(leaves_m) == len(leaves_c)
    for a, c in zip(leaves_m, leaves_c):
        assert a.shape == c.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32),
                                   rtol=2e-2, atol=2e-2)


# -- paged KV vs contiguous ----------------------------------------------------

def _paged_setup(model, batch, max_len, page_size, *, quantized=False):
    """Paged cache + dense per-slot block tables (slot i owns pages
    ``1 + i*nb .. 1 + (i+1)*nb``; page 0 stays the trash page)."""
    cfg = model.cfg
    kinds = sorted(({blk for blk, _ in cfg.head_layers()} |
                    {blk for blk, _ in cfg.group_pattern()}) & {"attn", "swa"})
    tables, num_pages = {}, {}
    for k in kinds:
        nb = -(-paged_kv_len(cfg, k, max_len) // page_size)
        tables[k] = jnp.arange(1, 1 + batch * nb,
                               dtype=jnp.int32).reshape(batch, nb)
        num_pages[k] = 1 + batch * nb
    cache = model.init_paged_cache(batch, num_pages, page_size,
                                   quantized=quantized)
    return cache, tables


@pytest.mark.parametrize("arch", [c[0] for c in CASES])
def test_paged_decode_bit_equals_contiguous(arch):
    """f32 paged attention is the same math as contiguous decode — logits
    must match to the bit, over enough steps to wrap the swa ring."""
    cfg = get_arch(arch, smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, max_len, page_size, steps = 2, 24, 4, 20

    contiguous = model.init_cache(b, max_len)
    paged, tables = _paged_setup(model, b, max_len, page_size)
    dense = jax.jit(model.decode_step)
    sparse = jax.jit(model.paged_decode_step,
                     static_argnames=("max_len",))

    rng = np.random.default_rng(0)
    pos_v = jnp.zeros((b,), jnp.int32)
    for t in range(steps):
        tok = jnp.asarray(rng.integers(0, cfg.vocab, (b, 1)), jnp.int32)
        ref, contiguous = dense(params, tok, jnp.int32(t), contiguous)
        got, paged = sparse(params, tok, pos_v, paged, tables,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        pos_v = pos_v + 1


# -- the continuous-batching engine --------------------------------------------

# (prompt_len, max_new, arrival_step): staggered arrivals force slot reuse
# and queueing — 6 requests through 3 slots
_TRACE = [(6, 5, 0), (10, 4, 0), (6, 3, 2), (1, 4, 3), (10, 6, 5), (6, 2, 9)]


def _trace_requests(vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, (s0,)).astype(np.int32),
                    max_new=n, arrival=float(arr))
            for i, (s0, n, arr) in enumerate(_TRACE)]


def _engine_tokens(model, params, reqs, *, quantized):
    engine = ServeEngine(model, params, max_batch=3, max_len=24,
                         page_size=4, quantized=quantized)
    report = engine.run(list(reqs), clock="steps")
    assert report["completed"] == len(reqs)
    # ONE compiled decode program across arrivals/evictions/slot reuse
    assert report["programs"]["serve_decode_step"] == 1
    return report, {c.rid: c.tokens for c in report["completions"]}


def test_engine_matches_batch1_greedy_generate():
    """Continuous batching must be invisible to each request: engine tokens
    equal batch-1 ``greedy_generate`` run in isolation, despite staggered
    admission, EOS-free budget eviction, and slot reuse."""
    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _trace_requests(cfg.vocab)

    report, tokens = _engine_tokens(model, params, reqs, quantized=False)
    for r in reqs:
        ref = greedy_generate(model, params, jnp.asarray(r.prompt[None]),
                              r.max_new, use_prefill=True)
        np.testing.assert_array_equal(tokens[r.rid], np.asarray(ref[0]),
                                      err_msg=f"rid {r.rid}")
    # one admission program per distinct prompt length, none for s0=1
    admit_progs = {k for k in report["programs"] if k.startswith("serve_admit")}
    assert admit_progs == {"serve_admit_s6", "serve_admit_s10"}


#: int8 KV rounding moves this model's logits by up to ~0.045 over a
#: generation; a greedy token may differ from the f32 argmax only inside it
INT8_LOGIT_TOL = 0.05


def _teacher_forced(model, params, reqs, tokens, length=24):
    """Per request, from one f32 forward of prompt + generated tokens: the
    gap (top logit − chosen token's logit) and the top-2 margin at every
    generated position."""
    toks = np.zeros((len(reqs), length), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, tokens[r.rid]])
        toks[i, :len(seq)] = seq
    logits = np.asarray(jax.jit(model.logits_all)(
        params, {"tokens": jnp.asarray(toks)}))
    out = {}
    for i, r in enumerate(reqs):
        gen = tokens[r.rid]
        rows = logits[i, r.s0 - 1:r.s0 - 1 + len(gen)]
        top2 = np.sort(rows, axis=-1)[:, -2:]
        gap = rows.max(-1) - rows[np.arange(len(gen)), gen]
        out[r.rid] = (gap, top2[:, 1] - top2[:, 0])
    return out


def test_engine_int8_kv_parity():
    """int8 KV pool reproduces f32 greedy tokens wherever the f32 choice is
    not a near tie, and every int8 token is the f32 teacher-forced argmax
    within the int8 rounding error (a near tie may flip, and the rest of
    that generation then follows its own, equally greedy, prefix)."""
    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _trace_requests(cfg.vocab)

    _, f32 = _engine_tokens(model, params, reqs, quantized=False)
    _, int8 = _engine_tokens(model, params, reqs, quantized=True)
    ref_f32 = _teacher_forced(model, params, reqs, f32)
    ref_int8 = _teacher_forced(model, params, reqs, int8)
    identical = 0
    for r in reqs:
        gap_f32, margin = ref_f32[r.rid]
        np.testing.assert_allclose(gap_f32, 0.0, atol=1e-5,
                                   err_msg=f"f32 rid {r.rid}")
        gap_int8, _ = ref_int8[r.rid]
        assert np.all(gap_int8 <= INT8_LOGIT_TOL), (r.rid, gap_int8)
        differ = np.nonzero(int8[r.rid] != f32[r.rid])[0]
        if differ.size == 0:
            identical += 1
        else:  # the first difference is a flipped f32 near tie
            assert margin[differ[0]] <= INT8_LOGIT_TOL, (r.rid, margin)
    assert identical >= len(reqs) - 1, identical


def test_engine_rejects_oversized_request():
    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=2, max_len=8, page_size=4)
    bad = Request(rid=0, prompt=np.zeros((6,), np.int32), max_new=4)
    with pytest.raises(ValueError, match="wrap their ring"):
        engine.sched.submit(bad)


# -- decode-step hygiene (analysis audits) -------------------------------------

def test_engine_step_jaxpr_is_clean():
    """The engine's compiled step must stage no host callbacks and bake no
    operand values: its lowering is identical across two occupancy states
    (so arrivals/evictions can never force a recompile)."""
    cfg = get_arch("qwen2_0_5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_batch=2, max_len=16, page_size=4)
    step = make_step(model, max_len=16, eos=-1)
    carry_a, tables_a = engine._carry, engine._tables

    assert audit_host_callbacks(step, params, carry_a, tables_a) == []

    carry_b = dict(
        carry_a,
        tok=carry_a["tok"] + 3,
        pos=carry_a["pos"] + 5,
        active=~carry_a["active"],
        limit=carry_a["limit"] + 7,
        temp=carry_a["temp"] + 0.5,
        step=carry_a["step"] + 11,
    )
    tables_b = {k: v.at[:, 0].set(1) for k, v in tables_a.items()}
    findings = audit_recompile(step, (params, carry_a, tables_a),
                               (params, carry_b, tables_b))
    assert findings == [], findings[0].message if findings else None


# -- host-side accounting: pages and slots -------------------------------------

def test_page_allocator_accounting():
    a = PageAllocator(num_pages=9)          # page 0 reserved for trash
    assert a.capacity == 8 and a.free_pages == 8
    p1 = a.alloc(3)
    p2 = a.alloc(2)
    assert len(set(p1) | set(p2)) == 5 and TRASH_PAGE not in p1 + p2
    assert a.used_pages == 5 and a.occupancy() == 5 / 8
    assert not a.can_alloc(4) and a.can_alloc(3)
    a.free(p1)
    assert a.free_pages == 6
    with pytest.raises(RuntimeError, match="double free"):
        a.free(p1 + p1)                     # more frees than capacity
    with pytest.raises(ValueError, match="invalid page"):
        a.free([TRASH_PAGE])                # the trash page is not poolable


def test_pages_needed_clamps_to_ring():
    # 10 tokens of context on a ring of 8 -> only ceil(8/4)=2 pages live
    assert pages_needed(7, 4, ring_len=8, page_size=4) == 2
    assert pages_needed(3, 2, ring_len=8, page_size=4) == 1
    assert pages_needed(1, 1, ring_len=8, page_size=4) == 1


def test_scheduler_fifo_and_release():
    sched = Scheduler(max_batch=2, page_size=4,
                      num_pages={"attn": 4}, ring_len={"attn": 16})
    def req(rid, s0, n):
        return Request(rid=rid, prompt=np.zeros((s0,), np.int32), max_new=n)

    sched.submit(req(0, 8, 4))      # needs ceil(11/4) = 3 pages (all of them)
    sched.submit(req(1, 8, 4))      # 3 more: does not fit beside rid 0
    sched.submit(req(2, 2, 2))      # 1 page — but FIFO: must wait behind 1
    a0 = sched.next_admission()
    assert a0.req.rid == 0 and len(a0.pages["attn"]) == 3
    assert sched.next_admission() is None       # head-of-line blocking
    assert sched.queued == 2 and sched.active_slots == 1
    assert sched.occupancy() == 1.0

    sched.release(a0.slot)
    a1 = sched.next_admission()
    assert a1.req.rid == 1 and a1.slot == a0.slot   # slot reuse
    assert sched.next_admission() is None       # rid 2 blocked on pages now
    sched.release(a1.slot)
    assert sched.next_admission().req.rid == 2

    with pytest.raises(ValueError, match="only has"):
        sched.submit(req(3, 12, 4))     # ceil(15/4) = 4 pages > capacity 3
    with pytest.raises(ValueError, match="wrap their ring"):
        sched.submit(req(4, 16, 9))     # 24 written positions > ring 16
