"""Shared neural building blocks (pure functions over param dicts)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import params as pr


def rmsnorm_decl(d: int):
    return {"scale": pr.ones((d,), ("embed",))}


def rmsnorm(p, x, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dtype)


def softcap(x, cap: float | None):
    """Gemma2-style logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# -- rotary position embeddings ---------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- GLU MLP ------------------------------------------------------------------

def glu_mlp_decl(d_model: int, d_ff: int):
    return {
        "w_gate": pr.normal((d_model, d_ff), ("embed", "mlp"), fan_in=d_model),
        "w_up": pr.normal((d_model, d_ff), ("embed", "mlp"), fan_in=d_model),
        "w_down": pr.normal((d_ff, d_model), ("mlp", "embed"), fan_in=d_ff),
    }


def weight_einsum(spec: str, x, w, dtype):
    """``einsum(spec, x, w)`` of an activation and a weight, in ``dtype``.

    A bfloat16 weight under a float32 compute type is read as stored: the
    activation is rounded to bfloat16 and the product accumulates in
    float32, which is what a default-precision float32 dot does on the TPU,
    without a convert of the weight on every call.  Any other weight is cast
    to ``dtype``.
    """
    if w.dtype == jnp.bfloat16 and jnp.dtype(dtype) == jnp.float32:
        return jnp.einsum(spec, x.astype(jnp.bfloat16), w,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, x.astype(dtype), w.astype(dtype))


def glu_mlp(p, x, compute_dtype=None):
    dt = compute_dtype or x.dtype
    x = x.astype(dt)
    gate = jax.nn.silu(weight_einsum("...d,df->...f", x, p["w_gate"], dt))
    up = weight_einsum("...d,df->...f", x, p["w_up"], dt)
    return weight_einsum("...f,fd->...d", gate * up, p["w_down"], dt)


# -- embeddings ---------------------------------------------------------------

def embedding_decl(vocab: int, d_model: int):
    return {"table": pr.normal((vocab, d_model), ("vocab", "embed"), fan_in=d_model)}


def embed(p, tokens, compute_dtype=None):
    out = jnp.take(p["table"], tokens, axis=0)
    return out.astype(compute_dtype) if compute_dtype else out


def chunked_logits_xent(x, emb_table, labels, mask=None, chunk: int = 512,
                        logit_softcap_val: float | None = None):
    """Cross-entropy over the vocab without materializing (B,S,V) at once.

    Scans over sequence chunks; each chunk computes logits (B,c,V) and its CE
    contribution, so peak memory is V·chunk instead of V·S.  Returns mean CE
    over unmasked positions.
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    n = s // chunk
    rem = s - n * chunk
    if mask is None:
        mask = jnp.ones((b, s), dtype=jnp.float32)
    mask = mask.astype(jnp.float32)

    def chunk_loss(xc, yc, mc):
        logits = jnp.einsum("bcd,vd->bcv", xc.astype(jnp.float32),
                            emb_table.astype(jnp.float32))
        if logit_softcap_val is not None:
            logits = logit_softcap_val * jnp.tanh(logits / logit_softcap_val)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * mc), jnp.sum(mc)

    def body(carry, inp):
        xc, yc, mc = inp
        tot, cnt = carry
        dl, dc = chunk_loss(xc, yc, mc)
        return (tot + dl, cnt + dc), None

    xs = (
        x[:, : n * chunk].reshape(b, n, chunk, d).transpose(1, 0, 2, 3),
        labels[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2),
        mask[:, : n * chunk].reshape(b, n, chunk).transpose(1, 0, 2),
    )
    (total, count), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), xs)
    if rem:
        dl, dc = chunk_loss(x[:, n * chunk:], labels[:, n * chunk:], mask[:, n * chunk:])
        total, count = total + dl, count + dc
    return total / jnp.maximum(count, 1.0)
