"""Pallas TPU kernel: the Mamba-1 selective scan with its state in VMEM.

Per channel d of ``d_inner`` and state index n of N, over tokens t:

    h[n, d] = exp(dt_t[d] a[d, n]) h[n, d] + dt_t[d] u_t[d] b_t[n]
    y_t[d]  = sum_n c_t[n] h[n, d] + D[d] u_t[d]

TPU adaptation: channels fill whole vregs.  A grid cell holds a block of
1024 channels, one (8, 128) float32 tile a token, and the state as N such
tiles, indexed by n on a leading axis: the sum over n is N vector
multiply-adds, with no reduction across sublanes.  b_t[n] and c_t[n] are
scalars, read from SMEM and multiplied in as such.

u, dt and y keep the model's (B, S, d_inner) layout in HBM.  A time block
first relays its u and dt rows, eight tokens at a time, into VMEM scratch
of one (8, 128) tile a token; then a loop runs the recurrence ``UNROLL``
tokens a step; then y goes back the same way.  The loop body is one or two
tokens, so the kernel lowers in a fraction of a second: a program lowers
it once for each Mamba layer shape.  The grid is (batch, channel blocks,
time blocks); the time axis is innermost and sequential, and the state
stays in VMEM scratch across its blocks, so HBM sees u, dt, b, c read once
and y written once, with the state read at the first time block and
written at the last.  No (S, d_inner, N) tensor exists anywhere.

Other layouts (the wrapper :func:`selective_scan` makes them): bc (B, S,
2N), b then c; a and the state (d_inner / 1024, N, 8, 128); D (d_inner /
1024, 8, 128).  S is padded to the time block with dt = u = 0, whose steps
leave the state exactly as it was (exp(0) = 1, no input), and d_inner to
the channel block with zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES, LANES = 8, 128
#: channels a grid cell holds: one (8, 128) float32 tile a token
BLOCK_D = SUBLANES * LANES
#: tokens relaid at a time: one (8, 1024) row block of u, dt and y
GROUP = 8
#: tokens a step of the recurrence's loop
UNROLL = 2


def _tree_sum(xs):
    while len(xs) > 1:
        xs = [a + b for a, b in zip(xs[::2], xs[1::2])] + xs[len(xs) & ~1:]
    return xs[0]


def _scan_kernel(bc_ref, u_ref, dt_ref, a_ref, d_ref, h0_ref, y_ref, hT_ref,
                 h_scr, u_scr, dt_scr, y_scr, *, n_state: int, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0]

    def groups(body):
        def step(g, carry):
            body(pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP))
            return carry
        jax.lax.fori_loop(0, block_t // GROUP, step, 0)

    def relay_in(rows):
        u_scr[rows] = u_ref[0, rows, :].reshape(GROUP, SUBLANES, LANES)
        dt_scr[rows] = dt_ref[0, rows, :].reshape(GROUP, SUBLANES, LANES)

    groups(relay_in)
    a = [a_ref[0, n] for n in range(n_state)]                # N x (8, 128)
    d_skip = d_ref[0]

    def token(t, hs):
        u, dt = u_scr[t], dt_scr[t]
        x = dt * u
        hs = tuple(jnp.exp(dt * a[n]) * hs[n] + bc_ref[0, t, n] * x
                   for n in range(n_state))
        y_scr[t] = _tree_sum([d_skip * u] + [
            bc_ref[0, t, n_state + n] * hs[n] for n in range(n_state)])
        return hs

    def tokens(i, hs):
        for k in range(UNROLL):
            hs = token(i * UNROLL + k, hs)
        return hs

    hs = tuple(h_scr[n] for n in range(n_state))
    hs = jax.lax.fori_loop(0, block_t // UNROLL, tokens, hs)
    for n in range(n_state):
        h_scr[n] = hs[n]

    def relay_out(rows):
        y_ref[0, rows, :] = y_scr[rows].reshape(GROUP, BLOCK_D)

    groups(relay_out)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _final():
        hT_ref[0, 0] = h_scr[...]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def selective_scan(u, dt, b, c, a, d, h0, *, block_t: int = 256,
                   interpret: bool = False):
    """u, dt: (B,S,di); b, c: (B,S,N); a: (di,N); d: (di,); h0: (B,di,N).

    Returns (y (B,S,di), hT (B,di,N)) in float32, as
    :func:`repro.kernels.mamba_scan.ref.selective_scan_ref` does."""
    f32 = jnp.float32
    bsz, s, di = u.shape
    n = a.shape[1]
    bt = min(block_t, _round_up(s, GROUP))
    assert bt % GROUP == 0 and GROUP % UNROLL == 0, bt
    sp, dip = _round_up(s, bt), _round_up(di, BLOCK_D)
    nb = dip // BLOCK_D

    def pad(x, axis, to):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, to - x.shape[axis])
        return jnp.pad(x.astype(f32), widths)

    def seq(x):                                              # (B, Sp, dip)
        return pad(pad(x, 1, sp), 2, dip)

    def state_tiles(x):                                      # (..., di, N) -> (..., nb, N, 8, 128)
        x = jnp.swapaxes(pad(x, x.ndim - 2, dip), -1, -2)
        x = x.reshape(*x.shape[:-1], nb, SUBLANES, LANES)
        return jnp.swapaxes(x, -4, -3)

    bc = pad(jnp.concatenate([b, c], axis=-1), 1, sp)        # (B, Sp, 2N)
    rows = pl.BlockSpec((1, bt, BLOCK_D), lambda i, j, t: (i, t, j))
    state = pl.BlockSpec((1, 1, n, SUBLANES, LANES),
                         lambda i, j, t: (i, j, 0, 0, 0))
    tiles = pltpu.VMEM((bt, SUBLANES, LANES), f32)
    kernel = functools.partial(_scan_kernel, n_state=n, block_t=bt)
    y, h_t = pl.pallas_call(
        kernel,
        grid=(bsz, nb, sp // bt),
        in_specs=[
            pl.BlockSpec((1, bt, 2 * n), lambda i, j, t: (i, t, 0),
                         memory_space=pltpu.SMEM),
            rows, rows,
            pl.BlockSpec((1, n, SUBLANES, LANES), lambda i, j, t: (j, 0, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda i, j, t: (j, 0, 0)),
            state,
        ],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, sp, dip), f32),
                   jax.ShapeDtypeStruct((bsz, nb, n, SUBLANES, LANES), f32)],
        scratch_shapes=[pltpu.VMEM((n, SUBLANES, LANES), f32),
                        tiles, tiles, tiles],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(bc, seq(u), seq(dt), state_tiles(a),
      pad(d, 0, dip).reshape(nb, SUBLANES, LANES), state_tiles(h0))
    h_t = jnp.swapaxes(h_t, 1, 2).reshape(bsz, n, dip)[:, :, :di]
    return y[:, :s, :di], jnp.swapaxes(h_t, 1, 2)
