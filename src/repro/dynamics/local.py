"""Local-update rounds with optional gradient tracking, as a Mixer wrapper.

DR-DSGD communicates every optimizer step; under sparse/expensive links the
practical regime is H **local** steps between consensus rounds (local SGD).
Plain local updates drift under heterogeneity — each node descends its own
distribution for H steps before consensus pulls it back.  Gradient tracking
(Ghiasvand et al., 2025; K-GT, Liu et al.) fixes the drift with a per-node
correction c_i added to every local step, steering local descent toward the
*globally averaged* direction.

:class:`LocalUpdateMixer` implements both as a wrapper around ANY v2 mixer,
expressed purely in parameter space (the wrapper sees post-update θ, never
gradients):

  every round:        θ̃_i = θ_i + c_i                (correction, GT only)
  local round:        nothing else happens (0 wire)
  consensus round:    θ⁺ = inner_mix(θ̃)              (the wrapped consensus)
                      Δ_i = θ̃_i − anchor_i           (window progress)
                      c_i ⁺= ((W Δ)_i − Δ_i) / H      (tracker exchange)
                      anchor_i = θ⁺_i

Over a window the correction accumulates (W Δ − Δ)/H — per local step, the
gap between the network-averaged window progress and the node's own — which
is exactly the parameter-space form of the gradient-tracking estimator
y_i ≈ (1/K) Σ_j g_j (the η·H factor is absorbed because everything lives in
parameter units).  At H = 1 the correction is a one-round-delayed consensus
boost; the interesting regime is H ≥ 2 under heterogeneity (benchmarks/
fig9_dynamics.py sweeps it).

State lives in ``CommState.track = (correction, anchor)`` — checkpointed
with the rest of the comm state (``repro.checkpoint``).  The wrapper owns
the round clock: ``CommState.rounds`` counts *optimizer steps*, and the
inner mixer's own increment is overwritten, so a wrapped compression
schedule anneals on the step clock (document-worthy: its ``warmup_rounds``
are steps, not consensus rounds, under H > 1).

Wire: local rounds report 0 bits; gradient tracking doubles a consensus
round's bits (the tracker Δ is exchanged full-precision alongside θ, the
classical 2× cost of GT), which is why GT requires an uncompressed inner
mixer (one with a pure ``mix_tree``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.comm.protocol import CommState, Mixer


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def _sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


class LocalUpdateMixer(Mixer):
    """Run H optimizer steps per consensus round, with optional tracking.

    Args:
      inner: any v2 :class:`Mixer` (compressed or not) — performs the
        consensus on rounds ``H-1, 2H-1, ...``.
      period: H ≥ 1; H = 1 degenerates to the inner mixer (plus tracking
        when enabled).
      gradient_tracking: carry the drift correction in ``CommState.track``.
        Requires an *uncompressed* inner mixer exposing a pure
        ``mix_tree`` (any identity-wire dense/gossip/hub stack); the
        tracker exchange doubles the consensus round's wire.
    """

    traced_wire = True  # 0 bits on local rounds

    def __init__(self, inner: Mixer, period: int,
                 gradient_tracking: bool = False):
        if period < 1:
            raise ValueError("period (H) must be >= 1")
        self.inner = inner
        self.period = int(period)
        self.gt = bool(gradient_tracking)
        if self.gt:
            if inner.compression is not None:
                raise ValueError(
                    "gradient tracking needs an uncompressed inner mixer "
                    "(the tracker exchange is full-precision; compose EF "
                    "compression with plain local updates instead)")
            base_mix = Mixer._mix
            supported = (type(inner).mix_tree is not Mixer.mix_tree
                         or type(inner)._mix is not base_mix)
            if not supported:
                raise ValueError(
                    f"{type(inner).__name__} has no pure mix_tree; gradient "
                    "tracking cannot exchange the tracker through it")

    @property
    def compression(self):
        return self.inner.compression

    # -- state ----------------------------------------------------------------

    def init_state(self, params) -> CommState:
        state = self.inner.init_state(params)
        if self.gt:
            corr = jax.tree.map(
                lambda x: jnp.zeros_like(x, jnp.float32), params)
            # anchor must not alias params (astype is a no-op on f32 leaves
            # and the scan driver donates the whole carry): force a copy
            anchor = jax.tree.map(
                lambda x: jnp.array(x, jnp.float32, copy=True), params)
            state = state._replace(track=(corr, anchor))
        return state

    def state_specs(self, param_specs) -> CommState:
        state = self.inner.state_specs(param_specs)
        if self.gt:
            state = state._replace(track=(param_specs, param_specs))
        return state

    def bytes_per_round(self, params) -> int:
        b = self.inner.bytes_per_round(params)
        return 2 * b if self.gt else b

    def wire_dtype_bytes(self, params):
        inner = self.inner.wire_dtype_bytes(params)
        if inner is None:
            return None
        # both lax.cond branches live in one program; the consensus branch
        # carries the inner wire, plus the full-precision tracker exchange
        # (mix_tree of an uncompressed inner: the same ops again) under GT
        return ({dt: 2 * b for dt, b in inner.items()} if self.gt
                else dict(inner))

    # -- the wrapper ----------------------------------------------------------

    def __call__(self, theta, state: CommState, *, round=None):
        track = state.track
        if self.gt:
            corr, anchor = track
            theta = jax.tree.map(
                lambda x, c: (x.astype(jnp.float32) + c).astype(x.dtype),
                theta, corr)

        def consensus(theta, st):
            mixed, st2 = self.inner(theta, st, round=round)
            if self.gt:
                with jax.named_scope("obs:consensus/tracker_exchange"):
                    delta = _sub(_f32(theta), anchor)
                    wdelta = self.inner.mix_tree(delta, st)
                corr2 = _add(corr, jax.tree.map(
                    lambda wd, d: (wd - d) / self.period, wdelta, delta))
                st2 = st2._replace(track=(corr2, _f32(mixed)),
                                   wire_bits=2.0 * st2.wire_bits)
            else:
                st2 = st2._replace(track=track)
            # the wrapper owns the clock: rounds counts optimizer steps
            return mixed, st2._replace(rounds=state.rounds + 1)

        def local(theta, st):
            return theta, st._replace(rounds=state.rounds + 1,
                                      wire_bits=jnp.float32(0.0),
                                      track=track)

        if self.period == 1:
            return consensus(theta, state)
        return jax.lax.cond(
            state.rounds % self.period == self.period - 1,
            consensus, local, theta, state)
