"""Pure-jnp oracle for the Mamba-1 selective scan: a ``lax.scan`` of one
token a step, the recurrence ``models/ssm.py`` has always run."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def selective_scan_ref(u, dt, b, c, a, d, h0):
    """u, dt: (B,S,di); b, c: (B,S,N); a: (di,N); d: (di,); h0: (B,di,N).

    h_t = exp(dt_t a) h_{t-1} + dt_t u_t b_t and y_t = h_t c_t + d u_t, in
    float32.  Returns (y (B,S,di), hT (B,di,N))."""
    f32 = jnp.float32

    def body(h, inp):
        u_t, dt_t, b_t, c_t = inp                            # (B,di),(B,di),(B,ds),(B,ds)
        da = jnp.exp(dt_t[..., None] * a)                    # (B,di,ds)
        dbu = dt_t[..., None] * b_t[:, None, :] * u_t[..., None].astype(f32)
        h = da * h + dbu
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    hT, ys = jax.lax.scan(
        body, h0,
        (u.transpose(1, 0, 2), dt.transpose(1, 0, 2),
         b.transpose(1, 0, 2), c.transpose(1, 0, 2)),
    )
    y = ys.transpose(1, 0, 2) + u.astype(f32) * d.astype(f32)
    return y, hT
