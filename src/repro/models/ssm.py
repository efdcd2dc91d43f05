"""Recurrent sequence blocks: RWKV6 ("Finch") time-mix and Mamba selective SSM.

Both are recurrences over time with a carried state, which is the exact
semantics the architectures define and gives O(1)-per-token decode for
`decode_32k` / `long_500k`.  RWKV6's is an explicit `lax.scan`, the
reference oracle for the `rwkv6_scan` Pallas kernel.  Mamba's selective scan
goes through `kernels/mamba_scan`: its `lax.scan` of one token a step is now
that kernel's reference (`ref.py`), which the CPU and the one-token decode
step run, while the TPU runs longer sequences through the kernel.

RWKV6's defining feature (arXiv:2404.05892) — the *data-dependent* per-channel
decay `w_t = exp(-exp(w0 + tanh(x̃_t A) B))` — is implemented faithfully, as is
the per-head bonus `u` and token-shift interpolation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan import ops as mamba_scan
from repro.models import params as pr
from repro.models.config import ArchConfig
from repro.models.layers import rmsnorm

# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------

_RWKV_LORA = 64


def rwkv_decl(cfg: ArchConfig):
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    h = d // hd
    ff = cfg.d_ff
    return {
        "time": {
            # token-shift interpolation weights per stream
            "mu_r": pr.constant((d,), ("embed",), 0.5),
            "mu_k": pr.constant((d,), ("embed",), 0.5),
            "mu_v": pr.constant((d,), ("embed",), 0.5),
            "mu_w": pr.constant((d,), ("embed",), 0.5),
            "mu_g": pr.constant((d,), ("embed",), 0.5),
            "w_r": pr.normal((d, d), ("embed", "hidden"), fan_in=d),
            "w_k": pr.normal((d, d), ("embed", "hidden"), fan_in=d),
            "w_v": pr.normal((d, d), ("embed", "hidden"), fan_in=d),
            "w_g": pr.normal((d, d), ("embed", "hidden"), fan_in=d),
            "w_o": pr.normal((d, d), ("hidden", "embed"), fan_in=d),
            # data-dependent decay: w0 + tanh(x A) B   (low-rank modulation)
            "decay_base": pr.constant((d,), ("embed",), -6.0),
            "decay_a": pr.normal((d, _RWKV_LORA), ("embed", None), fan_in=d),
            "decay_b": pr.normal((_RWKV_LORA, d), (None, "embed"), fan_in=_RWKV_LORA),
            "bonus": pr.zeros((h, hd), (None, None)),
        },
        "chan": {
            "mu_k": pr.constant((d,), ("embed",), 0.5),
            "mu_r": pr.constant((d,), ("embed",), 0.5),
            "w_k": pr.normal((d, ff), ("embed", "mlp"), fan_in=d),
            "w_v": pr.normal((ff, d), ("mlp", "embed"), fan_in=ff),
            "w_r": pr.normal((d, d), ("embed", "hidden"), fan_in=d),
        },
    }


def rwkv_init_state(cfg: ArchConfig, batch: int):
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    h = d // hd
    return {
        "x_time": jnp.zeros((batch, d), cfg.compute_dtype),   # prev token (time-mix)
        "x_chan": jnp.zeros((batch, d), cfg.compute_dtype),   # prev token (chan-mix)
        "wkv": jnp.zeros((batch, h, hd, hd), jnp.float32),    # per-head state
    }


def _rwkv_time_step(p, x_t, x_prev, s, cfg: ArchConfig):
    """One token of RWKV6 time-mix. x_t,x_prev: (B,d); s: (B,H,hd,hd)."""
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    h = d // hd
    dt = cfg.compute_dtype
    f32 = jnp.float32

    def shift(mu):
        return x_prev + (x_t - x_prev) * mu.astype(x_t.dtype)

    r = jnp.einsum("bd,dh->bh", shift(p["mu_r"]), p["w_r"].astype(dt))
    k = jnp.einsum("bd,dh->bh", shift(p["mu_k"]), p["w_k"].astype(dt))
    v = jnp.einsum("bd,dh->bh", shift(p["mu_v"]), p["w_v"].astype(dt))
    g = jax.nn.silu(jnp.einsum("bd,dh->bh", shift(p["mu_g"]), p["w_g"].astype(dt)))
    # data-dependent decay (the RWKV6 novelty)
    wx = shift(p["mu_w"]).astype(f32)
    wmod = jnp.tanh(wx @ p["decay_a"].astype(f32)) @ p["decay_b"].astype(f32)
    w = jnp.exp(-jnp.exp(p["decay_base"].astype(f32) + wmod))   # (B,d) in (0,1)

    rh = r.reshape(-1, h, hd).astype(f32)
    kh = k.reshape(-1, h, hd).astype(f32)
    vh = v.reshape(-1, h, hd).astype(f32)
    wh = w.reshape(-1, h, hd)
    u = p["bonus"].astype(f32)

    kv = jnp.einsum("bhk,bhv->bhkv", kh, vh)
    out = jnp.einsum("bhk,bhkv->bhv", rh, s + u[None, :, :, None] * kv)
    s_new = wh[..., None] * s + kv
    out = (out.reshape(-1, d) * g.astype(f32)).astype(dt)
    return jnp.einsum("bh,hd->bd", out, p["w_o"].astype(dt)), s_new


def _rwkv_chan_step(p, x_t, x_prev, cfg: ArchConfig):
    dt = cfg.compute_dtype

    def shift(mu):
        return x_prev + (x_t - x_prev) * mu.astype(x_t.dtype)

    k = jnp.einsum("bd,df->bf", shift(p["mu_k"]), p["w_k"].astype(dt))
    v = jnp.einsum("bf,fd->bd", jnp.square(jax.nn.relu(k)), p["w_v"].astype(dt))
    r = jax.nn.sigmoid(jnp.einsum("bd,dh->bh", shift(p["mu_r"]), p["w_r"].astype(dt)))
    return r * v


def rwkv_forward(p, x, cfg: ArchConfig, state=None):
    """Full-sequence RWKV6 block (time-mix + channel-mix with residuals).

    x: (B, S, D). Returns (y, final_state). Uses one scan over time for the
    wkv recurrence; token shifts are computed in parallel via jnp.roll-style
    padding.
    """
    b, s, d = x.shape
    if state is None:
        state = rwkv_init_state(cfg, b)

    # --- time mix
    x_prev_seq = jnp.concatenate([state["x_time"][:, None], x[:, :-1]], axis=1)

    def time_body(carry, inp):
        s_wkv = carry
        xt, xp = inp
        out, s_new = _rwkv_time_step(p["time"], xt, xp, s_wkv, cfg)
        return s_new, out

    wkv_state, t_out = jax.lax.scan(
        time_body, state["wkv"],
        (x.transpose(1, 0, 2), x_prev_seq.transpose(1, 0, 2)),
    )
    x = x + t_out.transpose(1, 0, 2)

    # --- channel mix (pointwise given shifted input: no scan needed)
    xc_prev = jnp.concatenate([state["x_chan"][:, None], x[:, :-1]], axis=1)
    c_out = _rwkv_chan_step(
        p["chan"],
        x.reshape(b * s, d),
        xc_prev.reshape(b * s, d),
        cfg,
    ).reshape(b, s, d)
    y = x + c_out
    new_state = {
        "x_time": x[:, -1] - t_out.transpose(1, 0, 2)[:, -1],  # pre-timemix input
        "x_chan": x[:, -1],
        "wkv": wkv_state,
    }
    return y, new_state


def rwkv_decode(p, x, cfg: ArchConfig, state):
    """Single-token step. x: (B,1,D)."""
    xt = x[:, 0]
    out, s_new = _rwkv_time_step(p["time"], xt, state["x_time"], state["wkv"], cfg)
    x1 = xt + out
    c = _rwkv_chan_step(p["chan"], x1, state["x_chan"], cfg)
    y = x1 + c
    return y[:, None], {"x_time": xt, "x_chan": x1, "wkv": s_new}


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — the recurrent half of Jamba
# ---------------------------------------------------------------------------

def mamba_decl(cfg: ArchConfig):
    d = cfg.d_model
    di = cfg.mamba_expand * d
    ds = cfg.mamba_d_state
    dc = cfg.mamba_d_conv
    dtr = cfg.mamba_dt_rank or max(1, (d + 15) // 16)
    return {
        "in_proj": pr.normal((d, 2 * di), ("embed", "hidden"), fan_in=d),
        "conv_w": pr.normal((di, dc), ("hidden", None), fan_in=dc),
        "conv_b": pr.zeros((di,), ("hidden",)),
        "x_proj": pr.normal((di, dtr + 2 * ds), ("hidden", None), fan_in=di),
        "dt_proj": pr.normal((dtr, di), (None, "hidden"), fan_in=dtr),
        "dt_bias": pr.zeros((di,), ("hidden",)),
        "a_log": pr.constant((di, ds), ("hidden", "state"), 0.0),
        "d_skip": pr.ones((di,), ("hidden",)),
        "out_proj": pr.normal((di, d), ("hidden", "embed"), fan_in=di),
        # Jamba's RMSNorm scales on the x_proj split (dt, B, C)
        "dt_norm": pr.ones((dtr,), (None,)),
        "b_norm": pr.ones((ds,), ("state",)),
        "c_norm": pr.ones((ds,), ("state",)),
    }


def mamba_init_state(cfg: ArchConfig, batch: int):
    di = cfg.mamba_expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1, di), cfg.compute_dtype),
        "ssm": jnp.zeros((batch, di, cfg.mamba_d_state), jnp.float32),
    }


def _mamba_ssm_scan(p, u, cfg: ArchConfig, h0):
    """Selective scan. u: (B,S,di) post-conv activations. Returns (y, hT).

    As in Jamba, dt, B and C are RMS-normed after ``x_proj``, before
    ``dt_proj`` and the scan.  The recurrence and the ``d_skip`` term run
    under the ``obs:ssm/scan`` scope: more than one token through the
    ``mamba_scan`` kernel's path, one token (the decode step) as a single
    plain step of its reference."""
    ds = cfg.mamba_d_state
    dtr = p["dt_proj"].shape[0]
    f32 = jnp.float32
    proj = jnp.einsum("bsd,dk->bsk", u.astype(f32), p["x_proj"].astype(f32))
    dt_low, bmat, cmat = jnp.split(proj, [dtr, dtr + ds], axis=-1)
    dt_low = rmsnorm({"scale": p["dt_norm"]}, dt_low, cfg.rmsnorm_eps)
    bmat = rmsnorm({"scale": p["b_norm"]}, bmat, cfg.rmsnorm_eps)
    cmat = rmsnorm({"scale": p["c_norm"]}, cmat, cfg.rmsnorm_eps)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_low, p["dt_proj"].astype(f32))
        + p["dt_bias"].astype(f32)
    )                                                        # (B,S,di)
    a = -jnp.exp(p["a_log"].astype(f32))                     # (di, ds)

    with jax.named_scope("obs:ssm/scan"):
        y, hT = mamba_scan.selective_scan(u, dt, bmat, cmat, a, p["d_skip"], h0)
    return y, hT


def _causal_conv(p, x, cfg: ArchConfig, conv_state=None):
    """Depthwise causal conv1d. x: (B,S,di)."""
    dc = cfg.mamba_d_conv
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], dc - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                   # (B, S+dc-1, di)
    w = p["conv_w"].astype(x.dtype)                          # (di, dc)
    out = sum(
        xp[:, i:i + x.shape[1]] * w[:, i] for i in range(dc)
    ) + p["conv_b"].astype(x.dtype)
    return out, xp[:, -(dc - 1):]


def mamba_forward(p, x, cfg: ArchConfig, state=None):
    """x: (B,S,D) -> (y, state)."""
    b = x.shape[0]
    if state is None:
        state = mamba_init_state(cfg, b)
    dt_ = cfg.compute_dtype
    di = cfg.mamba_expand * cfg.d_model
    xz = jnp.einsum("bsd,dk->bsk", x.astype(dt_), p["in_proj"].astype(dt_))
    u, z = jnp.split(xz, [di], axis=-1)
    with jax.named_scope("obs:ssm/conv"):
        u, conv_state = _causal_conv(p, u, cfg, state["conv"])
    u = jax.nn.silu(u)
    y, ssm_state = _mamba_ssm_scan(p, u, cfg, state["ssm"])
    y = y.astype(dt_) * jax.nn.silu(z)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(dt_))
    return out.astype(x.dtype), {"conv": conv_state, "ssm": ssm_state}


def mamba_decode(p, x, cfg: ArchConfig, state):
    """Single token: reuse forward with S=1 (conv state carries history)."""
    return mamba_forward(p, x, cfg, state)
