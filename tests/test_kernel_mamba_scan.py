"""Mamba selective-scan kernel (interpret mode) against its ``lax.scan``
reference: outputs and final state over ragged lengths and widths, time-block
invariance, exact padding, the custom VJP, and the wrapper's path choice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mamba_scan import ops
from repro.kernels.mamba_scan.kernel import selective_scan
from repro.kernels.mamba_scan.ref import selective_scan_ref


def _case(seed, b, s, di, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (b, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) - 1.0)
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    a = -jnp.exp(0.5 * jax.random.normal(ks[4], (di, n)))
    d = jax.random.normal(ks[5], (di,))
    h0 = jax.random.normal(ks[6], (b, di, n))
    return u, dt, bm, cm, a, d, h0


def _assert_close(got, want, tol=2e-5):
    scale = float(np.max(np.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=tol * scale)


@pytest.mark.parametrize("b,s,di,n", [
    (1, 1, 128, 16),
    (2, 7, 128, 8),
    (1, 37, 256, 16),
    (2, 300, 128, 4),
    (1, 300, 200, 16),      # d_inner not a multiple of the lanes
    (1, 40, 2048, 16),      # two channel blocks
])
def test_matches_ref(b, s, di, n):
    args = _case(s + di, b, s, di, n)
    y, h = selective_scan(*args, block_t=128, interpret=True)
    y_ref, h_ref = selective_scan_ref(*args)
    assert y.shape == (b, s, di) and h.shape == (b, di, n)
    _assert_close(y, y_ref)
    _assert_close(h, h_ref)


def test_time_block_invariance():
    """The state carried across time blocks gives what one block gives."""
    args = _case(0, 1, 512, 256, 16)
    whole = selective_scan(*args, block_t=512, interpret=True)
    for bt in (8, 64, 128):
        part = selective_scan(*args, block_t=bt, interpret=True)
        for got, want in zip(part, whole):
            _assert_close(got, want, tol=1e-6)


def test_padding_with_zero_dt_leaves_the_state_bit_for_bit():
    """Steps with dt = u = 0 multiply the state by exp(0) = 1 and add 0, so
    the kernel's padding of a ragged length changes no bit of hT."""
    u, dt, bm, cm, a, d, h0 = _case(3, 1, 128, 128, 16)
    zeros = jnp.zeros((1, 128, 128))
    padded = (jnp.concatenate([u, zeros], 1), jnp.concatenate([dt, zeros], 1),
              jnp.concatenate([bm, bm], 1), jnp.concatenate([cm, cm], 1),
              a, d, h0)
    for scan in (selective_scan_ref,
                 lambda *x: selective_scan(*x, block_t=128, interpret=True)):
        _, h = scan(u, dt, bm, cm, a, d, h0)
        _, h_padded = scan(*padded)
        np.testing.assert_array_equal(np.asarray(h_padded), np.asarray(h))


def test_custom_vjp_gives_the_references_gradients():
    args = _case(5, 2, 24, 128, 8)
    wy = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 128))
    wh = jax.random.normal(jax.random.PRNGKey(7), (2, 128, 8))

    def loss(scan):
        def f(*x):
            y, h = scan(*x)
            return jnp.sum(y * wy) + jnp.sum(h * wh)
        return f

    kernel = loss(lambda *x: ops.selective_scan(*x, interpret=True))
    grads = jax.grad(kernel, argnums=tuple(range(7)))(*args)
    want = jax.grad(loss(selective_scan_ref), argnums=tuple(range(7)))(*args)
    for got, ref in zip(grads, want):
        _assert_close(got, ref, tol=1e-5)


@pytest.mark.parametrize("seq_len,kw,backend,path", [
    (1, {"interpret": True}, "tpu", "ref"),    # the decode step's plain step
    (1, {}, "tpu", "ref"),
    (2, {"interpret": True}, "cpu", "interpret"),
    (8192, {}, "cpu", "ref"),
    (8192, {}, "tpu", "pallas"),
])
def test_scan_path(seq_len, kw, backend, path, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.scan_path(seq_len, **kw) == path
