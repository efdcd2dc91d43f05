"""Plain reference of one compressed gossip round: CHOCO error feedback over
a stochastically rounded int8 wire with per-block scales (the job's
``compress: int8``, ``error_feedback: true``).  It imports nothing of the
system under test.

The round, for node i with pre-mix parameters x_i (after its DR-SGD step),
public copy h_i (zero before the first round) and the job's mixing matrix W:

    delta_i = x_i - h_i                                  the innovation
    s_ib    = max_{n in block b} |delta_in| / qmax       one scale per block
    q_in    = floor(delta_in / s_ib + u_in),  u ~ U[0, 1) stochastic rounding
    h'_i    = h_i + s_i q_i                              the new public copy
    x'_i    = x_i + gamma (sum_j W_ij h'_j - h'_i)       the CHOCO correction

Only s and q cross the wire; each node keeps the running mix sum_j W_ij h_j
of the public copies it has received.

What the rounding guarantees.  floor(y + u) - y lies in (u - 1, u], so the
public copy's error e_i = h'_i - x_i = s_i (q_i - delta_i / s_i) lies within
one quantum: |e_in| < s_ib, elementwise.  With sum_j W_ij = 1,

    x'_i = x_i + gamma (sum_j W_ij x_j - x_i) + gamma (sum_j W_ij e_j - e_i)

so the post-mix parameters lie within
gamma (sum_{j != i} W_ij s_j + (1 - W_ii) s_i) of the exact float32 mix of
what was mixed: for the ring's Metropolis weights (1/3 each) that is
gamma (s_{i-1} + s_{i+1} + 2 s_i) / 3, not sum_j W_ij s_j.  The rounding is
unbiased (E e = 0), and with the fraction of delta / s spread evenly over
[0, 1), E e^2 = E[f (1 - f)] s^2 = s^2 / 6: the root mean square of e over
a leaf is sqrt(1/6) = 0.408 of that of its quanta.  Since the round's mean
over nodes is exact (W doubly stochastic), the node mean of the parameters
moves by the node mean of the DR-SGD steps alone, whatever the wire.

The check (``bench/harness/train.py``) reads the program's parameters x'
and public copies h' after each of its first rounds and holds them, leaf by
leaf, against the pre-mix parameters x that the reference computes from the
program's parameters before the round:

* ``wire_rms_ratio`` -- rms(h' - x) / rms(s): 0.408 for the wire as stated;
  a coarser wire (fewer levels, a larger scale) or a public copy that does
  not accumulate (error feedback off) reads more;
* ``choco_rms_ratio`` -- rms(x' - x - gamma (W h' - h')) / rms(s): the
  correction as stated, given the public copies; a neighbour left out or no
  exchange reads more.

Largest elementwise gaps are not compared: over 10^9 elements the rounding
error itself comes within 10^-5 of its bound, so any rounding between the
reference's pre-mix parameters and the program's would read as a breach.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: fine layout of a block past one lane row and one (8, 128) tile
_LANES, _TILE = 128, 1024


def block_len(d: int, block_d: int) -> int:
    """Elements per scale block of a node's leaf of ``d`` elements on a wire
    of ``block_d``-element blocks: the leaf is cut into
    n = ceil(d / block_d) blocks of ceil(d / n) elements, rounded up to whole
    128-element rows past one row and whole 1024-element tiles past one
    tile (the last block takes the rest)."""
    n = -(-d // block_d)
    b = -(-d // n)
    if b > _TILE:
        return -(-b // _TILE) * _TILE
    return b if b <= _LANES else -(-b // _LANES) * _LANES


def quanta(delta, block_d: int, qmax: float):
    """(K, d) innovations -> (K, d): each element's block scale."""
    k, d = delta.shape
    b = block_len(d, block_d)
    n = -(-d // b)
    x = jnp.pad(jnp.abs(delta), ((0, 0), (0, n * b - d))).reshape(k, n, b)
    s = jnp.max(x, axis=2, keepdims=True) / qmax
    return jnp.broadcast_to(s, x.shape).reshape(k, n * b)[:, :d]


def round_stats(x, h_prev, h_new, x_new, w, *, gamma: float, block_d: int,
                qmax: float) -> dict:
    """Sums of squares over one leaf (all nodes; (K, d) float32 each) of the
    quanta, the public copies' errors and the correction's gaps."""
    s = quanta(x - h_prev, block_d, qmax)
    mixed = jnp.einsum("kl,ld->kd", w, h_new, precision=jax.lax.Precision.HIGHEST)
    gap = x_new - (x + gamma * (mixed - h_new))
    return {"quanta": jnp.sum(jnp.square(s)),
            "wire": jnp.sum(jnp.square(h_new - x)),
            "choco": jnp.sum(jnp.square(gap))}


def simulate_round(x, h_prev, w, key, *, gamma: float, block_d: int,
                   qmax: float = 127.0, scale: float = 1.0,
                   error_feedback: bool = True):
    """One round of the wire above in plain jnp, for calibration: the
    reference put in the program's place.  ``scale`` multiplies every block
    scale (2: the scale doubled), ``qmax`` sets the levels (7: an int4 wire),
    ``error_feedback=False`` sends C(x) with no public copy kept, and a W
    with a link zeroed leaves that neighbour out.  Returns (x', h')."""
    base = h_prev if error_feedback else jnp.zeros_like(h_prev)
    delta = x - base
    s = quanta(delta, block_d, qmax) * scale
    u = jax.random.uniform(key, delta.shape, jnp.float32)
    q = jnp.clip(jnp.floor(delta / jnp.where(s > 0, s, 1.0) + u), -qmax, qmax)
    h_new = base + q * s
    mixed = jnp.einsum("kl,ld->kd", w, h_new, precision=jax.lax.Precision.HIGHEST)
    return x + gamma * (mixed - h_new), h_new
