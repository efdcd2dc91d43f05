"""Pallas TPU kernels: fused int8 quantize / dequantize-accumulate for the
compressed gossip consensus step.

The compressed round is  quantize → ppermute(payload) → dequantize-accumulate.
The ppermute stays an XLA collective (it is already optimal on the torus);
these two kernels fuse everything around it so the *only* HBM-resident wire
buffer is the int8 payload plus its per-block float32 scales:

* ``quantize_tiles`` — one pass over x: each (node, block) tile
  computes its own absmax scale in VMEM and stochastically rounds
  ``floor(x/scale + u)`` into int8.  Per-block scales are strictly finer
  than per-node scales, so the kernel path is never less accurate than the
  jnp compressor it replaces.  An optional per-node send mask zeroes the
  payload and scales of masked rows.
* ``dequant_accumulate_tiles`` — one pass over the received payload:
  ``acc + w_node · (q · scale_block)`` without materializing the
  dequantized float32 message.

Layout.  A row of D elements is cut into ``n_blk = ceil(D / block_d)``
scale blocks of :func:`block_len` elements (the last one zero-padded).  The
kernels see each block as one ``(rows, lanes)`` tile of a
``(K·n_blk, rows, lanes)`` view, with ``lanes = 128`` (``= block`` for
blocks of at most 128) and ``rows`` a multiple of 8 once a block spans
more than 8 lane rows, so every block shape equals the view's last two
dims and meets the TPU (8, 128) / (32, 128) tiling rule at any width,
ragged or not.  The int8 payload stays in that tile view from the
quantize kernel over the wire to the dequantize kernel:
reshaping int8 between tiled layouts is a relayout the TPU compiler pays
for at every leaf.  Scales come out as ``(K, n_blk)``; per-node scalars
(qmax, the send mask, the receive weights) ride in SMEM.  The uniforms
``u`` are an input (generated from the traced PRNG key) so the kernel is
bit-exact against ``ref.py`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: a block longer than this is whole (8, 128) f32 tiles, so the tile view of
#: an unpadded f32 row is a bitcast
_TILE = 8 * LANES


def block_len(d: int, n_blk: int) -> int:
    """Elements per scale block when a row of ``d`` is cut into ``n_blk``
    blocks: ``ceil(d / n_blk)``, rounded up to whole 128-lane rows past one
    lane row and to whole (8, 128) tiles past one tile.  For every
    ``n = num_blocks(d, block_d)``, ``ceil(d / block_len(d, n)) == n``, so
    a receiver recovers the layout from the scales' shape alone."""
    b = -(-d // n_blk)
    if b > _TILE:
        return -(-b // _TILE) * _TILE
    return b if b <= LANES else -(-b // LANES) * LANES


def num_blocks(d: int, block_d: int) -> int:
    """Scale blocks per row of ``d`` for a requested block length (the
    kernel and ``ref.py`` emit exactly this many scales per node)."""
    unit = _TILE if block_d > _TILE else LANES
    if d > block_d > LANES and block_d % unit:
        raise ValueError(f"block_d={block_d} must be <= {LANES}, a multiple "
                         f"of {LANES} up to {_TILE}, or of {_TILE} beyond")
    return -(-d // block_d)


def tile_shape(k: int, d: int, n_blk: int) -> tuple[int, int, int]:
    """Shape of the ``(K·n_blk, rows, lanes)`` tile view of a (K, D) array."""
    b = block_len(d, n_blk)
    lanes = min(b, LANES)
    return (k * n_blk, b // lanes, lanes)


def to_tiles(x, n_blk: int):
    """(K, D) -> (K·n_blk, rows, lanes), one scale block per leading index
    (the ragged tail zero-padded: zeros change no absmax and quantize to 0)."""
    k, d = x.shape
    shape = tile_shape(k, d, n_blk)
    pad = n_blk * shape[1] * shape[2] - d
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(shape)


def from_tiles(t, k: int, d: int):
    """Inverse of :func:`to_tiles` (drops the padded tail)."""
    return t.reshape(k, -1)[:, :d]


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _tile_spec(shape):
    return pl.BlockSpec((1,) + shape[1:], lambda i: (i, 0, 0))


def _quantize_kernel(qmax_ref, mask_ref, x_ref, u_ref, q_ref, scale_ref, *,
                     n_blk):
    # qmax is a traced scalar so an adaptive schedule can switch the int8
    # wire to int4 (qmax 127 -> 7) without recompiling; the per-node send
    # mask zeroes a masked sender's payload and scale (nothing on the wire)
    qmax = qmax_ref[0]
    m = mask_ref[pl.program_id(0) // n_blk]
    x = x_ref[...]
    absmax = jnp.max(jnp.max(jnp.abs(x), axis=2, keepdims=True), axis=1,
                     keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    y = jnp.floor(x / scale + u_ref[...])
    q_ref[...] = (jnp.clip(y, -qmax, qmax) * m).astype(jnp.int8)
    scale_ref[...] = jnp.broadcast_to(scale * m, scale_ref.shape)


def _dequant_acc_kernel(w_ref, q_ref, scale_ref, acc_ref, o_ref, *, n_blk):
    w = w_ref[pl.program_id(0) // n_blk]
    deq = q_ref[...].astype(jnp.float32) * scale_ref[...]
    o_ref[...] = (acc_ref[...].astype(jnp.float32) + w * deq
                  ).astype(o_ref.dtype)


def quantize_tiles(x, u, *, qmax=127, block_d: int = 65536, mask=None,
                   interpret: bool = False):
    """x, u: (K, D) -> (q int8 tiles (K·n_blk, rows, lanes), scales f32
    (K, n_blk)).

    ``qmax`` may be a python int or a traced f32 scalar (schedule-driven);
    ``mask`` (K,) in {0, 1}, traced, zeroes masked rows' payload and scales.
    """
    k, d = x.shape
    n_blk = num_blocks(d, block_d)
    xt = to_tiles(x.astype(jnp.float32), n_blk)
    ut = to_tiles(u.astype(jnp.float32), n_blk)
    if mask is None:
        mask = jnp.ones((k,), jnp.float32)
    scale_shape = (xt.shape[0], 1, xt.shape[2])
    q, s = pl.pallas_call(
        functools.partial(_quantize_kernel, n_blk=n_blk),
        grid=(xt.shape[0],),
        in_specs=[_smem(), _smem(), _tile_spec(xt.shape),
                  _tile_spec(xt.shape)],
        out_specs=[_tile_spec(xt.shape), _tile_spec(scale_shape)],
        out_shape=[jax.ShapeDtypeStruct(xt.shape, jnp.int8),
                   jax.ShapeDtypeStruct(scale_shape, jnp.float32)],
        name="quant_gossip_quantize",
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(qmax, jnp.float32), (1,)),
      jnp.reshape(mask.astype(jnp.float32), (k,)), xt, ut)
    return q, s[:, 0, 0].reshape(k, n_blk)


def dequant_accumulate_tiles(acc, q, scales, w, *, interpret: bool = False):
    """acc (K, D) f32, q int8 tiles, scales (K, n_blk), w (K,) -> (K, D):
    ``acc + w · (q · scale)`` per (node, block)."""
    k, d = acc.shape
    n_blk = scales.shape[1]
    acct = to_tiles(acc, n_blk)
    st = jnp.broadcast_to(scales.reshape(k * n_blk, 1, 1).astype(jnp.float32),
                          (k * n_blk, 1, q.shape[2]))
    out = pl.pallas_call(
        functools.partial(_dequant_acc_kernel, n_blk=n_blk),
        grid=(q.shape[0],),
        in_specs=[_smem(), _tile_spec(q.shape), _tile_spec(st.shape),
                  _tile_spec(acct.shape)],
        out_specs=_tile_spec(acct.shape),
        out_shape=jax.ShapeDtypeStruct(acct.shape, acc.dtype),
        name="quant_gossip_dequant_acc",
        interpret=interpret,
    )(jnp.reshape(w.astype(jnp.float32), (k,)), q, st, acct)
    return from_tiles(out, k, d)

