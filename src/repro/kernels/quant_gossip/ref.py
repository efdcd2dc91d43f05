"""Pure-jnp oracles for the fused quantize / dequantize-accumulate kernels.

Bit-exact against the Pallas kernels given the same uniforms ``u`` (both
compute ``clip(floor(x/scale + u))`` with a per-(node, block) absmax scale
over the block layout of :func:`~repro.kernels.quant_gossip.kernel.block_len`).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.quant_gossip.kernel import block_len, num_blocks


def _blocked(x, n_blk):
    """(K, D) -> (K, n_blk, block), the ragged tail zero-padded."""
    k, d = x.shape
    b = block_len(d, n_blk)
    return jnp.pad(x, ((0, 0), (0, n_blk * b - d))).reshape(k, n_blk, b)


def _unblocked(xb, d):
    return xb.reshape(xb.shape[0], -1)[:, :d]


def quantize_blockwise_ref(x, u, *, qmax=127, block_d: int = 65536):
    """x, u: (K, D) -> (q int8 (K, D), scales f32 (K, n_blk)).

    ``qmax`` may be a python int or a traced f32 scalar.
    """
    k, d = x.shape
    n_blk = num_blocks(d, block_d)
    xb = _blocked(x.astype(jnp.float32), n_blk)
    absmax = jnp.max(jnp.abs(xb), axis=2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    y = jnp.floor(xb / scale + _blocked(u.astype(jnp.float32), n_blk))
    q = jnp.clip(y, -qmax, qmax).astype(jnp.int8)
    return _unblocked(q, d), scale.reshape(k, n_blk)


def dequantize_blockwise_ref(q, scales):
    """(K, D) int8 + (K, n_blk) scales -> (K, D) float32."""
    d = q.shape[1]
    out = _blocked(q.astype(jnp.float32), scales.shape[1]) * scales[:, :, None]
    return _unblocked(out, d)


def dequant_accumulate_ref(acc, q, scales, w):
    """acc + w[:, None] * dequantize(q, scales)."""
    w = jnp.reshape(w, (-1,))
    return (acc.astype(jnp.float32)
            + w[:, None] * dequantize_blockwise_ref(q, scales)).astype(acc.dtype)


def masked_quantize_blockwise_ref(x, u, mask, *, qmax=127,
                                  block_d: int = 65536):
    """Masked-sender oracle: masked rows emit zero payload and zero scales."""
    q, scales = quantize_blockwise_ref(x, u, qmax=qmax, block_d=block_d)
    m = jnp.reshape(mask.astype(jnp.float32), (-1, 1))
    q = jnp.where(m > 0, q, jnp.int8(0))
    return q, scales * m


def masked_dequant_accumulate_ref(acc, q, scales, w, mask):
    """acc + mask·w·dequantize(q, scales); masked links add exactly 0."""
    m = jnp.reshape(mask.astype(jnp.float32), (-1,))
    return dequant_accumulate_ref(acc, q, scales, jnp.reshape(w, (-1,)) * m)
