"""GQA attention: chunked online-softmax (train/prefill) + cached decode.

The chunked path is the XLA (non-Pallas) implementation used for smoke tests
and the dry-run; it never materializes the (S, S) score matrix — memory per
step is q_chunk x kv_chunk — and doubles as the reference oracle for the
Pallas ``flash_attention`` kernel.

Supports: grouped KV heads, RoPE (or no positional encoding: jamba),
optional QKV bias (qwen2), sliding-window masking (h2o-danube / gemma2
local layers), attention-score soft-capping (gemma2), and ring-buffer KV
caches for decode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import params as pr
from repro.models.config import ArchConfig
from repro.models.layers import apply_rope, weight_einsum


def attention_decl(cfg: ArchConfig):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    decl = {
        "wq": pr.normal((d, h, hd), ("embed", "q_heads", None), fan_in=d),
        "wk": pr.normal((d, kv, hd), ("embed", "kv_heads", None), fan_in=d),
        "wv": pr.normal((d, kv, hd), ("embed", "kv_heads", None), fan_in=d),
        "wo": pr.normal((h, hd, d), ("q_heads", None, "embed"), fan_in=h * hd),
    }
    if cfg.qkv_bias:
        decl["bq"] = pr.zeros((h, hd), ("q_heads", None))
        decl["bk"] = pr.zeros((kv, hd), ("kv_heads", None))
        decl["bv"] = pr.zeros((kv, hd), ("kv_heads", None))
    return decl


def init_kv_cache(cfg: ArchConfig, batch: int, seq_len: int, kind: str):
    """KV cache shapes for one attention layer.

    Sliding-window layers keep only ``window`` entries (ring buffer) — this is
    what makes `long_500k` feasible for SWA architectures.
    """
    t = seq_len
    if kind == "swa" and cfg.sliding_window is not None:
        t = min(t, cfg.sliding_window)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, t, kvh, hd), cfg.compute_dtype),
        "v": jnp.zeros((batch, t, kvh, hd), cfg.compute_dtype),
    }


def _mask_bias(q_pos, k_pos, window: int | None):
    """(…, q, k) additive mask: causal, optionally sliding-window."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]  # q_pos - k_pos
    ok = diff >= 0
    if window is not None:
        ok &= diff < window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _scores(q, k, scale, cap):
    # q: (B, qc, KV, G, hd)  k: (B, kc, KV, hd) -> (B, KV, G, qc, kc)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    return s


def chunked_attention(q, k, v, q_positions, k_positions, *, window=None,
                      softcap_val=None, q_chunk=512, kv_chunk=1024):
    """Online-softmax attention. q: (B,S,KV,G,hd); k,v: (B,T,KV,hd).

    Returns (B, S, KV, G, hd) in q.dtype. Never materializes (S,T) scores.
    """
    b, s, kvh, g, hd = q.shape
    t = k.shape[1]
    scale = 1.0 / (hd ** 0.5)
    qc = min(q_chunk, s)
    kc = min(kv_chunk, t)
    if s % qc or t % kc:
        # fall back to a single chunk if shapes don't tile (small smoke runs)
        qc = s if s % qc else qc
        kc = t if t % kc else kc
    nq, nk = s // qc, t // kc

    qs = q.reshape(b, nq, qc, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_positions.reshape(nq, qc)
    ks = k.reshape(b, nk, kc, kvh, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kc, kvh, hd).transpose(1, 0, 2, 3, 4)
    kp = k_positions.reshape(nk, kc)

    def per_q_chunk(carry, q_in):
        q_blk, qp_blk = q_in  # (B,qc,KV,G,hd), (qc,)

        def per_kv_chunk(inner, k_in):
            m, l, acc = inner
            k_blk, v_blk, kp_blk = k_in
            sc = _scores(q_blk, k_blk, scale, softcap_val)  # (B,KV,G,qc,kc)
            sc = sc + _mask_bias(qp_blk, kp_blk, window)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(per_kv_chunk, (m0, l0, a0), (ks, vs, kp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,KV,G,qc,hd)
        return carry, out.transpose(0, 3, 1, 2, 4)            # (B,qc,KV,G,hd)

    _, outs = jax.lax.scan(per_q_chunk, (), (qs, qp))         # (nq,B,qc,KV,G,hd)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, kvh, g, hd)
    return out.astype(q.dtype)


def _project_qkv(p, x, cfg: ArchConfig, positions):
    dt = cfg.compute_dtype
    x = x.astype(dt)
    q = weight_einsum("bsd,dhk->bshk", x, p["wq"], dt)
    k = weight_einsum("bsd,dhk->bshk", x, p["wk"], dt)
    v = weight_einsum("bsd,dhk->bshk", x, p["wv"], dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.rope_theta is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_forward(p, x, cfg: ArchConfig, *, kind: str, positions,
                      return_kv: bool = False):
    """Train/prefill path. x: (B,S,D); positions: (S,)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kvh
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = q.reshape(b, s, kvh, g, hd)
    window = cfg.sliding_window if kind == "swa" else None
    out = chunked_attention(
        q, k, v, positions, positions, window=window,
        softcap_val=cfg.attn_softcap,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
    )
    out = out.reshape(b, s, h, hd)
    proj = weight_einsum("bshk,hkd->bsd", out, p["wo"], out.dtype)
    if return_kv:
        return proj, {"k": k, "v": v}
    return proj


# -- paged KV cache (repro.serve) ---------------------------------------------
#
# A paged cache stores one layer's KV in a shared pool of fixed-size pages,
# ``(num_pages, page_size, kvh, hd)``, addressed through a per-slot block
# table ``(B, n_blocks) int32``: logical ring position ``s`` of slot ``i``
# lives at ``pool[table[i, s // page_size], s % page_size]``.  Evicting a
# request frees its pages back to the pool without reshaping anything; the
# table is a *traced* operand, so admissions/evictions never recompile.
#
# Quantized pools keep the payload in int8 with per-(token, block) float32
# scales — the same blockwise-absmax layout as the ``quant_gossip`` wire
# kernels (``KV_SCALE_BLOCK`` = 128 keeps a scale per int8 tile lane group),
# but with round-to-nearest (u = 0.5) instead of stochastic rounding: a KV
# write must be deterministic so an A/B replay generates identical tokens.

#: feature-dim block one float32 scale covers in a quantized pool (the 128
#: lanes of the (32, 128) int8 TPU tile; rows = page slots)
KV_SCALE_BLOCK = 128


def paged_kv_len(cfg: ArchConfig, kind: str, max_len: int) -> int:
    """Logical ring length of a paged layer (sliding window caps "swa")."""
    t = max_len
    if kind == "swa" and cfg.sliding_window is not None:
        t = min(t, cfg.sliding_window)
    return t


def kv_scale_blocks(cfg: ArchConfig, scale_block: int = KV_SCALE_BLOCK) -> int:
    """Scales per token a quantized pool stores (mirrors the kernel layout)."""
    from repro.kernels.quant_gossip.kernel import num_blocks

    return num_blocks(cfg.n_kv_heads * cfg.resolved_head_dim, scale_block)


def init_paged_kv(cfg: ArchConfig, num_pages: int, page_size: int, *,
                  quantized: bool, scale_block: int = KV_SCALE_BLOCK):
    """Zeroed page pool for one attention layer (page 0 is the trash page)."""
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (num_pages, page_size, kvh, hd)
    if not quantized:
        return {"k": jnp.zeros(shape, cfg.compute_dtype),
                "v": jnp.zeros(shape, cfg.compute_dtype)}
    s = kv_scale_blocks(cfg, scale_block)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros((num_pages, page_size, s), jnp.float32),
            "v_scale": jnp.zeros((num_pages, page_size, s), jnp.float32)}


def quantize_kv_rows(x, *, scale_block: int = KV_SCALE_BLOCK):
    """(N, D) -> (q int8 (N, D), scales f32 (N, S)), round-to-nearest.

    Reuses the ``quant_gossip`` blockwise-quantize Pallas kernel (the jnp
    oracle on the CPU) with u = 0.5, i.e. ``round(x / scale)`` — the cache
    write path is deterministic, unlike the stochastically-rounded gossip
    wire.
    """
    from repro.kernels.quant_gossip import ops as qops

    x = x.astype(jnp.float32)
    u = jnp.full(x.shape, 0.5, jnp.float32)
    return qops.quantize_blockwise(x, u, qmax=127, block_d=scale_block)


def _expand_kv_scales(scales, d: int):
    """(..., S) per-block scales -> (..., D) per-element multipliers."""
    from repro.kernels.quant_gossip.kernel import block_len

    b = block_len(d, scales.shape[-1])
    return jnp.repeat(scales, b, axis=-1)[..., :d]


def paged_kv_write(pool, k, v, page_ids, offsets, *,
                   scale_block: int = KV_SCALE_BLOCK):
    """Scatter one new token per slot into the pool.

    k, v: (B, kvh, hd); page_ids, offsets: (B,) int32 (inactive slots point
    at the trash page, so their writes land nowhere that is ever read).
    """
    b, kvh, hd = k.shape
    if "k_scale" not in pool:
        return {"k": pool["k"].at[page_ids, offsets].set(
                    k.astype(pool["k"].dtype)),
                "v": pool["v"].at[page_ids, offsets].set(
                    v.astype(pool["v"].dtype))}
    qk, sk = quantize_kv_rows(k.reshape(b, kvh * hd), scale_block=scale_block)
    qv, sv = quantize_kv_rows(v.reshape(b, kvh * hd), scale_block=scale_block)
    return {
        "k": pool["k"].at[page_ids, offsets].set(qk.reshape(b, kvh, hd)),
        "v": pool["v"].at[page_ids, offsets].set(qv.reshape(b, kvh, hd)),
        "k_scale": pool["k_scale"].at[page_ids, offsets].set(sk),
        "v_scale": pool["v_scale"].at[page_ids, offsets].set(sv),
    }


def paged_kv_gather(pool, table, t: int, out_dtype):
    """Read (k, v) (B, t, kvh, hd) through the block table, dequantizing.

    ``table`` (B, n_blocks) int32 with n_blocks * page_size >= t.  Unwritten
    logical slots come back as whatever the page holds — callers mask
    validity by position exactly as the contiguous decode path does.
    """
    ps, kvh, hd = pool["k"].shape[1:]
    d = kvh * hd

    def one(name):
        g = pool[name][table]                       # (B, NB, ps, kvh, hd)
        b, nb = g.shape[:2]
        g = g.reshape(b, nb * ps, kvh, hd)[:, :t]
        if name + "_scale" not in pool:
            return g.astype(out_dtype)
        s = pool[name + "_scale"][table]            # (B, NB, ps, S)
        s = s.reshape(b, nb * ps, -1)[:, :t]
        full = g.astype(jnp.float32).reshape(b, t, d) * _expand_kv_scales(s, d)
        return full.reshape(b, t, kvh, hd).astype(out_dtype)

    return one("k"), one("v")


def paged_attention_decode(p, x, cfg: ArchConfig, *, kind: str, pool, table,
                           pos, max_len: int,
                           scale_block: int = KV_SCALE_BLOCK):
    """Single-token decode against a paged pool, per-slot positions.

    x: (B, 1, D); pos: (B,) int32 (each serving slot at its own position);
    pool: one layer's page pool; table: (B, n_blocks) int32.  Returns
    (out (B, 1, D), new_pool).  Identical math to :func:`attention_decode` —
    with an f32 pool and lockstep positions the logits are bit-equal.
    """
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kvh
    t = paged_kv_len(cfg, kind, max_len)
    ps = pool["k"].shape[1]
    q, k, v = _project_qkv(p, x, cfg, pos[:, None])

    slot = pos % t  # ring position, exactly as the contiguous cache
    page_ids = jnp.take_along_axis(table, (slot // ps)[:, None], axis=1)[:, 0]
    with jax.named_scope("obs:serve/kv_write"):
        pool = paged_kv_write(pool, k[:, 0], v[:, 0], page_ids, slot % ps,
                              scale_block=scale_block)
    with jax.named_scope("obs:serve/kv_gather"):
        ck, cv = paged_kv_gather(pool, table, t, pool["k"].dtype
                                 if "k_scale" not in pool
                                 else cfg.compute_dtype)

    with jax.named_scope("obs:serve/attend"):
        idx = jnp.arange(t)
        valid = (idx[None, :] <= pos[:, None]) | (pos[:, None] >= t)  # (B, t)
        scale = 1.0 / (hd ** 0.5)
        qh = q.reshape(b, 1, kvh, g, hd)
        sc = _scores(qh, ck, scale, cfg.attn_softcap)         # (B,KV,G,1,T)
        sc = jnp.where(valid[:, None, None, None, :], sc, -1e30)
        att = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bkgqs,bskh->bqkgh", att, cv.astype(jnp.float32))
    out = out.reshape(b, 1, h, hd).astype(x.dtype)
    proj = weight_einsum("bshk,hkd->bsd", out, p["wo"], out.dtype)
    return proj, pool


def attention_decode(p, x, cfg: ArchConfig, *, kind: str, cache, pos):
    """Single-token decode. x: (B,1,D); pos: scalar int; cache: {k,v}.

    Returns (out (B,1,D), new_cache). Sliding-window layers use the cache as a
    ring buffer over ``window`` slots.
    """
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kvh
    t = cache["k"].shape[1]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions)

    slot = pos % t  # full caches (t == seq_len) and ring buffers alike
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))

    # validity: slots <= pos are filled; once pos >= t the ring is full.
    idx = jnp.arange(t)
    valid = (idx <= pos) | (pos >= t)
    scale = 1.0 / (hd ** 0.5)
    qh = q.reshape(b, 1, kvh, g, hd)
    sc = _scores(qh, ck, scale, cfg.attn_softcap)             # (B,KV,G,1,T)
    sc = jnp.where(valid[None, None, None, None, :], sc, -1e30)
    att = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", att, cv.astype(jnp.float32))
    out = out.reshape(b, 1, h, hd).astype(x.dtype)
    proj = weight_einsum("bshk,hkd->bsd", out, p["wo"], out.dtype)
    return proj, {"k": ck, "v": cv}
