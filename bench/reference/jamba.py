"""Plain reference of the Jamba family (AI21 Jamba, Jamba2), from the paper
(arXiv:2403.19887) and HF transformers' ``modeling_jamba.py``, in
``jax.numpy``.

One sequence at a time, no cache and no batching.  Layer ``i`` is attention
iff ``i % attn_layer_period == attn_layer_offset``, Mamba otherwise; every
layer is ``x += mixer(norm1(x)); x += mlp(norm2(x))``, then a final norm and
the head.  It reads a configuration file (``bench/configs/<name>.json``) and
a parameter tree laid out as ``bench/weights.py`` makes it for the family
(``bench/families/jamba.py``).  It imports nothing of the system under test.

* RMSNorm: ``x * rsqrt(mean(x^2) + eps) * scale``.
* Attention: q/k/v projections without bias and without any positional
  encoding, grouped KV heads (each KV head serves ``H / KV`` query heads),
  softmax of ``q.k / sqrt(hd)`` over the causal past, output projection.
  Computed for blocks of queries against the whole sequence, so that 8448
  tokens fit one chip.
* Mamba mixer (``JambaMambaMixer``'s sequential path): ``in_proj`` splits
  into u and the gate z; u goes through the causal depthwise convolution of
  width ``d_conv`` with its bias and a SiLU; ``x_proj`` gives dt, B and C,
  each RMS-normed; ``dt = softplus(dt_proj(dt) + dt_bias)``,
  ``A = -exp(a_log)``; a sequential scan over the tokens,
  ``h_t = exp(dt_t A) h_{t-1} + (dt_t B_t) u_t``, ``y_t = h_t C_t + D u_t``;
  ``out_proj(y * silu(z))``.
* MLP: ``down(silu(gate(x)) * up(x))``.  Head: the tied embedding table or
  its own ``lm_head``.

Departures from HF Jamba: parameters are stored float32 (bfloat16 in the
source); the tree is laid out as the program's (the period's layers stacked
on a leading axis, the convolution's weight (d_inner, d_conv)); only
``num_experts`` 1 is taken (no router or experts); only the causal mask
(no padding masks); logits are given at the positions asked for.

``dtype`` is the compute type: float32 for the reference (the caller sets
the matmul precision), bfloat16 for the control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: largest block of queries the attention computes at once
Q_BLOCK = 512


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _attention(cfg, p, x, dtype):
    n = x.shape[0]
    q = jnp.einsum("ld,dhk->lhk", x, p["wq"].astype(dtype))
    k = jnp.einsum("ld,dhk->lhk", x, p["wk"].astype(dtype))
    v = jnp.einsum("ld,dhk->lhk", x, p["wv"].astype(dtype))
    heads, kvh, hd = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, heads // kvh, axis=1)   # query head j reads KV head j // group
    v = jnp.repeat(v, heads // kvh, axis=1)
    blk = math.gcd(n, Q_BLOCK)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk)
        s = jnp.einsum("qhk,shk->hqs", qb, k).astype(jnp.float32) / jnp.sqrt(
            jnp.float32(hd))
        past = (start + jnp.arange(blk))[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(past[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("hqs,shk->qhk", a, v)

    o = jax.lax.map(block, jnp.arange(0, n, blk)).reshape(n, heads, hd)
    return jnp.einsum("qhk,hkd->qd", o, p["wo"].astype(dtype))


def _mamba(cfg, p, x, dtype):
    n = x.shape[0]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    r, ds, dc = cfg["mamba_dt_rank"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    eps = cfg["rms_norm_eps"]
    xz = x @ p["in_proj"].astype(dtype)
    u, z = xz[:, :di], xz[:, di:]
    # tap j of the causal convolution reads position t - (d_conv - 1) + j
    padded = jnp.concatenate([jnp.zeros((dc - 1, di), dtype), u])
    w = p["conv_w"].astype(dtype)
    u = sum(padded[j:j + n] * w[:, j] for j in range(dc)) + p["conv_b"].astype(dtype)
    u = jax.nn.silu(u)
    proj = u @ p["x_proj"].astype(dtype)
    dt = _rmsnorm(proj[:, :r], p["dt_norm"], eps)
    b = _rmsnorm(proj[:, r:r + ds], p["b_norm"], eps)
    c = _rmsnorm(proj[:, r + ds:], p["c_norm"], eps)
    dt = jax.nn.softplus(dt @ p["dt_proj"].astype(dtype) + p["dt_bias"].astype(dtype))
    a = -jnp.exp(p["a_log"].astype(dtype))                      # (di, ds)

    def step(h, inp):
        u_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h + dt_t[:, None] * b_t[None, :] * u_t[:, None]
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((di, ds), dtype), (u, dt, b, c))
    y = (y + u * p["d_skip"].astype(dtype)) * jax.nn.silu(z)
    return y @ p["out_proj"].astype(dtype)


def _mlp(p, x, dtype):
    g = x @ p["w_gate"].astype(dtype)
    u = x @ p["w_up"].astype(dtype)
    return (jax.nn.silu(g) * u) @ p["w_down"].astype(dtype)


def hidden(cfg: dict, params: dict, tokens, dtype=jnp.float32):
    """Final normed hidden states (L, D) of one token sequence (L,)."""
    eps = cfg["rms_norm_eps"]
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    x = params["embedding"]["table"][tokens].astype(dtype)
    for i in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[i // period],
                         params["groups"][f"l{i % period}"])
        mixer = _attention if i % period == offset else _mamba
        x = x + mixer(cfg, p["mix"], _rmsnorm(x, p["norm1"]["scale"], eps), dtype)
        x = x + _mlp(p["ffn"], _rmsnorm(x, p["norm2"]["scale"], eps), dtype)
    return _rmsnorm(x, params["final_norm"]["scale"], eps)


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
