"""Jitted wrappers for the fused quantize-gossip kernels.

On the TPU these run the compiled Pallas kernels, and with ``interpret=True``
the Pallas interpreter; only the CPU backend runs the bit-identical jnp
oracle in their place (:func:`repro.kernels.kernel_path`), so the compressed
gossip mixer works unchanged in CPU simulation.

``quant_gossip_round`` composes one full compressed matching exchange —
quantize → ppermute(int8 payload + scales) → dequantize-accumulate — for use
inside ``shard_map``; the full-precision message never exists on the wire,
and the int8 payload keeps the kernel's tile view end to end (``*_tiles``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import kernel_path
from repro.kernels.quant_gossip import kernel as _k
from repro.kernels.quant_gossip import ref as _r


@functools.partial(jax.jit,
                   static_argnames=("block_d", "interpret", "use_kernel"))
def quantize_tiles(x, u, *, qmax=127, block_d: int = 65536, mask=None,
                   interpret: bool = False, use_kernel: bool = True):
    """(K, D) f32 -> (q int8 tiles (K·n_blk, rows, lanes), per-block scales
    f32 (K, n_blk)): the wire payload, in the kernel's tile view.

    ``qmax`` is traced (not static), so schedule-driven int8 -> int4 rate
    switches reuse one compiled program; so is the optional per-node send
    ``mask`` (K,) in {0, 1}: masked rows put nothing on the wire.
    """
    path = kernel_path(use_kernel, interpret)
    if path != "ref":
        return _k.quantize_tiles(x, u, qmax=qmax, block_d=block_d, mask=mask,
                                 interpret=path == "interpret")
    if mask is None:
        q, s = _r.quantize_blockwise_ref(x, u, qmax=qmax, block_d=block_d)
    else:
        q, s = _r.masked_quantize_blockwise_ref(x, u, mask, qmax=qmax,
                                                block_d=block_d)
    return _k.to_tiles(q, s.shape[1]), s


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def dequant_accumulate_tiles(acc, q, scales, w, mask=None, *,
                             interpret: bool = False, use_kernel: bool = True):
    """acc + w·dequant(q, scales) for a tile-view payload, one fused pass.

    ``w`` and the optional link ``mask`` are per-node (K,) traced operands;
    a masked link contributes exactly ``acc`` (bitwise): its weight is 0.
    """
    w = jnp.reshape(jnp.asarray(w, jnp.float32), (-1,))
    if mask is not None:
        w = w * jnp.reshape(jnp.asarray(mask, jnp.float32), (-1,))
    path = kernel_path(use_kernel, interpret)
    if path != "ref":
        return _k.dequant_accumulate_tiles(acc, q, scales, w,
                                           interpret=path == "interpret")
    k, d = acc.shape
    return _r.dequant_accumulate_ref(acc, _k.from_tiles(q, k, d), scales, w)


@functools.partial(jax.jit, static_argnames=("d",))
def dequantize_tiles(q, scales, d: int):
    """Tile-view payload -> (K, d) float32 (``q · scale`` per block)."""
    k, n_blk = scales.shape
    out = q.astype(jnp.float32) * scales.reshape(k * n_blk, 1, 1)
    return _k.from_tiles(out, k, d)


def quantize_blockwise(x, u, *, qmax=127, block_d: int = 65536, mask=None,
                       interpret: bool = False, use_kernel: bool = True):
    """(K, D) f32 -> (q int8 (K, D), per-block scales f32 (K, n_blk)).

    ``mask`` (K,) in {0, 1} is traced, like ``qmax``: masked rows emit a
    zero payload and zero scales, so per-round topology faults reuse one
    compiled program.  Two wires are built from this kernel: the memoryless
    dynamic gossip round quantizes θ per matching (``quant_gossip_round``),
    and the error-feedback dynamic wire quantizes the *innovation delta*
    θ − θ̂ once per round (``KernelInt8Quantizer.compress_masked``) with the
    node-level any-live-link sender mask — a fully-masked node's θ̂ stays
    frozen exactly as the jnp path's masked input does.
    """
    q, s = quantize_tiles(x, u, qmax=qmax, block_d=block_d, mask=mask,
                          interpret=interpret, use_kernel=use_kernel)
    return _k.from_tiles(q, *x.shape), s


def dequant_accumulate(acc, q, scales, w, mask=None, *,
                       interpret: bool = False, use_kernel: bool = True):
    """acc + mask·w·dequant(q, scales), one fused pass over the (K, D) int8
    payload.  Per-node weights and the optional link mask are traced; a
    masked link contributes exactly ``acc`` bitwise."""
    return dequant_accumulate_tiles(acc, _k.to_tiles(q, scales.shape[1]),
                                    scales, w, mask, interpret=interpret,
                                    use_kernel=use_kernel)


def quant_gossip_round(x, acc, weight, axis, perm, key, *, mask=None,
                       qmax: int = 127, block_d: int = 65536,
                       interpret: bool = False, use_kernel: bool = True):
    """One compressed matching exchange (must run inside shard_map).

    Args:
      x: (K_local, D) local block to transmit.
      acc: (K_local, D) accumulator the received message is combined into.
      weight: (K_local,) receive weights W_{i, perm(i)}.
      axis: mesh axis name(s) carrying the node dimension.
      perm: static list of (src, dst) ppermute pairs.
      key: PRNG key for the stochastic-rounding uniforms.
      mask: optional traced (K_local,) per-round link mask, applied at both
        ends: masked senders emit a zero payload and masked receivers
        combine exactly 0, so every round of a dynamic topology reuses one
        compiled program.

    Returns acc + weight · dequant(ppermute(quantize(x))).
    """
    with jax.named_scope("obs:kernel/quant_gossip_round"):
        u = jax.random.uniform(key, x.shape, jnp.float32)
        q, scales = quantize_tiles(x, u, qmax=qmax, block_d=block_d,
                                   mask=mask, interpret=interpret,
                                   use_kernel=use_kernel)
        q = jax.lax.ppermute(q, axis, perm)
        scales = jax.lax.ppermute(scales, axis, perm)
        return dequant_accumulate_tiles(acc, q, scales, weight, mask,
                                        interpret=interpret,
                                        use_kernel=use_kernel)
