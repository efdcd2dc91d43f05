"""Plain reference of the two configurations' decoder, from the published
architecture (Qwen2, Mistral-type), in ``jax.numpy``.

One sequence at a time, the whole causal score matrix per layer, layers in a
``lax.scan`` with each layer recomputed in the backward pass, so a
2048-token sequence at full width fits one chip.  It reads a configuration
file (``bench/configs/<name>.json``) and a parameter tree laid out as
``bench/weights.py`` makes it.  It imports nothing of the system under test.

* RMSNorm: ``x * rsqrt(mean(x^2) + eps) * scale``.
* Attention: q/k/v projections (with bias where ``attention_bias``), RoPE on
  q and k in the half-rotation form with ``rope_theta``, grouped KV heads
  (each KV head serves ``H / KV`` query heads), softmax of ``q.k / sqrt(hd)``
  over the causal (and, where ``use_sliding_window``, windowed) past,
  output projection.
* MLP: ``down(silu(gate(x)) * up(x))``.
* Head: tied embedding table or its own ``lm_head``.

``dtype`` is the compute type: float32 (at ``highest`` matmul precision,
set by the caller) for the reference, bfloat16 for the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, pos, theta):
    """x: (L, heads, hd); the first and second halves of hd rotate as pairs."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]        # (L, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2].astype(jnp.float32), x[..., hd // 2:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _layer(cfg, dtype, x, p):
    eps = cfg["rms_norm_eps"]
    n = x.shape[0]
    pos = jnp.arange(n)
    h = _rmsnorm(x, p["norm1"]["scale"], eps)
    m = p["mix"]
    q = jnp.einsum("ld,dhk->lhk", h, m["wq"].astype(dtype))
    k = jnp.einsum("ld,dhk->lhk", h, m["wk"].astype(dtype))
    v = jnp.einsum("ld,dhk->lhk", h, m["wv"].astype(dtype))
    if cfg.get("attention_bias"):
        q = q + m["bq"].astype(dtype)
        k = k + m["bk"].astype(dtype)
        v = v + m["bv"].astype(dtype)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    heads, kvh, hd = q.shape[1], k.shape[1], q.shape[2]
    group = heads // kvh
    k = jnp.repeat(k, group, axis=1)                  # query head j reads KV head j // group
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k).astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))
    past = pos[:, None] - pos[None, :]
    ok = past >= 0
    if cfg.get("use_sliding_window"):
        ok &= past < cfg["sliding_window"]
    s = jnp.where(ok[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(dtype)
    o = jnp.einsum("hqs,shk->qhk", a, v)
    x = x + jnp.einsum("qhk,hkd->qd", o, m["wo"].astype(dtype))
    h2 = _rmsnorm(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    g = jnp.einsum("ld,df->lf", h2, f["w_gate"].astype(dtype))
    u = jnp.einsum("ld,df->lf", h2, f["w_up"].astype(dtype))
    x = x + jnp.einsum("lf,fd->ld", jax.nn.silu(g) * u, f["w_down"].astype(dtype))
    return x


def hidden(cfg: dict, params: dict, tokens, dtype=jnp.float32):
    """Final normed hidden states (L, D) of one token sequence (L,)."""
    x = params["embedding"]["table"][tokens].astype(dtype)
    body = jax.checkpoint(lambda x, p: (_layer(cfg, dtype, x, p), None))
    x, _ = jax.lax.scan(body, x, params["groups"]["l0"])
    return _rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def head_table(cfg: dict, params: dict):
    if cfg.get("tie_word_embeddings"):
        return params["embedding"]["table"]
    return params["lm_head"]["table"]


def logits_at(cfg: dict, params: dict, tokens, positions, dtype=jnp.float32):
    """Logits (P, V) at ``positions`` of one sequence: position t predicts
    token t + 1."""
    h = hidden(cfg, params, tokens, dtype)[positions]
    return jnp.einsum("pd,vd->pv", h, head_table(cfg, params).astype(dtype)
                      ).astype(jnp.float32)


def loss(cfg: dict, params: dict, row, dtype=jnp.float32):
    """Mean next-token cross-entropy of one row (L + 1,) of tokens."""
    h = hidden(cfg, params, row[:-1], dtype)
    logits = jnp.einsum("ld,vd->lv", h, head_table(cfg, params).astype(dtype)
                        ).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, row[1:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


def batch_loss(cfg: dict, params: dict, rows, dtype=jnp.float32):
    """Mean over the rows (B, L + 1) of a node's batch."""
    return jnp.mean(jax.vmap(lambda r: loss(cfg, params, r, dtype))(rows))
