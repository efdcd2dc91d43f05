"""Checkify sanitizer: runtime invariant checks inside the jitted step.

The static passes (``repro.analysis.audit`` / ``.lint``) catch structural
hazards; this module catches *numerical* protocol violations while the real
program runs, using ``jax.experimental.checkify`` so the checks live inside
the compiled step (no host syncs, no second code path):

* the round's mixing matrix W is doubly stochastic (rows AND columns sum to
  1 — Assumption 5; a dropout renormalization bug shows up here first),
* the CHOCO error-feedback invariant Σ_i ŝ_i = Σ_i θ̂_i holds within a drift
  bound (the incremental ``hat_mix`` cache is consistent with the public
  copies it claims to mix — the correctness oracle for the adaptive re-base),
* the mixed parameters are finite post-dequantize-accumulate,
* the traced codec rate stays inside its container (qmax ≤ 127 in the int8
  wire, kept-ratio in (0, 1]),
* dynamic link masks are exactly {0, 1}.

``step_checks`` is injected by ``build_train_step(..., sanitize=True)`` and
only emits ``checkify.check`` calls — it returns nothing and must run inside
a ``checkify.checkify``-transformed function (the trainer wraps its step and
scan drivers when ``sanitize=True``).  With ``sanitize=False`` nothing is
staged and the program is bit-exact to a build without this module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import checkify

from repro.comm.composed import ComposedMixer
from repro.comm.topology import gather_round_vectors
from repro.comm.transport import GossipTransport
from repro.comm.wire import CodecWire

# Doubly-stochastic tolerance: renormalized dropout weights accumulate a few
# ulps per row; 1e-4 is ~3 orders above observed f32 noise and well below
# any real renormalization bug (a single dropped-and-unreturned link shifts
# a row sum by O(W_ij) ~ 1e-1).
_W_ATOL = 1e-4
# CHOCO drift: |Σ(ŝ − θ̂)| per leaf, relative to the public-copy scale.
_DRIFT_RTOL = 1e-3
_DRIFT_ATOL = 1e-3


def _unwrap(mixer):
    """Peel wrapper mixers (LocalUpdateMixer, RepeatMixer) to the consensus
    operator that owns W and the codec."""
    seen = set()
    while hasattr(mixer, "inner") and id(mixer) not in seen:
        seen.add(id(mixer))
        mixer = mixer.inner
    return mixer


def _round_w(target: ComposedMixer, prev_comm):
    """The (K, K) mixing matrix the round ran under, or None where the
    stack has no topology layer (identity; static gossip keeps its W in
    the decomposition weights)."""
    if target.topo is None:
        return None
    if target.topo.time_varying:
        # the traced W_r of THIS round (prev_comm.rounds is the counter
        # value the mixer read when it gathered weights)
        return target.topo.round_w(prev_comm.rounds)
    return jnp.asarray(target._round_w(prev_comm), jnp.float32)


def _gathers_masks(target: ComposedMixer) -> bool:
    """Time-varying gossip on the identity or masked-quant wire gathers
    {0, 1} link masks out of W_r; the clocked EF stack gets no such check."""
    return (target.topo.time_varying
            and isinstance(target.transport, GossipTransport)
            and not isinstance(target.wire, CodecWire))


def check_doubly_stochastic(w) -> None:
    rows = jnp.sum(w, axis=1)
    cols = jnp.sum(w, axis=0)
    checkify.check(
        jnp.max(jnp.abs(rows - 1.0)) < _W_ATOL,
        "sanitize: W rows do not sum to 1 (max |err| = {e}) — the mixing "
        "matrix is not doubly stochastic (Assumption 5)",
        e=jnp.max(jnp.abs(rows - 1.0)))
    checkify.check(
        jnp.max(jnp.abs(cols - 1.0)) < _W_ATOL,
        "sanitize: W cols do not sum to 1 (max |err| = {e}) — the mixing "
        "matrix is not doubly stochastic (Assumption 5)",
        e=jnp.max(jnp.abs(cols - 1.0)))


def check_finite_tree(tree, what: str) -> None:
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            continue
        checkify.check(
            jnp.all(jnp.isfinite(x)),
            "sanitize: non-finite values in " + what
            + jax.tree_util.keystr(path))


def check_choco_invariant(comm) -> None:
    """Σ_i ŝ_i == Σ_i θ̂_i per leaf: the mixed public copies are a mixing
    of the public copies (W doubly stochastic preserves the node sum; the
    incremental delta recursion must preserve it too)."""
    if comm.hat == () or comm.hat_mix == ():
        return
    for (path, h), s in zip(jax.tree_util.tree_leaves_with_path(comm.hat),
                            jax.tree.leaves(comm.hat_mix)):
        hs = jnp.sum(h.astype(jnp.float32), axis=0)
        ss = jnp.sum(s.astype(jnp.float32), axis=0)
        scale = jnp.max(jnp.abs(hs))
        drift = jnp.max(jnp.abs(ss - hs))
        checkify.check(
            drift <= _DRIFT_ATOL + _DRIFT_RTOL * scale,
            "sanitize: CHOCO invariant violated at hat"
            + jax.tree_util.keystr(path)
            + " — max |sum(s) - sum(theta_hat)| = {d} (scale {s0}); the "
            "hat_mix cache is stale or the delta recursion dropped mass",
            d=drift, s0=scale)


def check_masks_binary(masks) -> None:
    for i, m in enumerate(masks):
        checkify.check(
            jnp.all((m == 0.0) | (m == 1.0)),
            "sanitize: matching %d link mask is not in {{0, 1}}" % i)


def check_rate_in_container(wire, prev_comm) -> None:
    compression = wire.compression
    if compression is None:
        return
    rate = wire.rate(prev_comm)
    if rate is None:
        return
    if compression.kind in ("int8", "int4"):
        checkify.check(
            (rate >= 1.0) & (rate <= 127.0),
            "sanitize: traced qmax {r} outside the int8 container [1, 127]",
            r=rate)
    else:
        checkify.check(
            (rate > 0.0) & (rate <= 1.0),
            "sanitize: traced kept-ratio {r} outside (0, 1]", r=rate)


def step_checks(mixer, prev_comm, theta_mixed, comm) -> None:
    """Stage every applicable invariant check for one consensus round.

    Args:
      mixer: the trainer's mixer (wrappers are unwrapped here).
      prev_comm: the CommState the round CONSUMED (its ``rounds`` counter
        selects the round's traced W).
      theta_mixed: the round's output parameters.
      comm: the CommState the round produced.
    """
    target = _unwrap(mixer)
    stack = isinstance(target, ComposedMixer)
    check_finite_tree(theta_mixed, "mixed params at ")
    w = _round_w(target, prev_comm) if stack else None
    if w is not None:
        check_doubly_stochastic(w)
        if _gathers_masks(target):
            _, _, masks = gather_round_vectors(w, target.transport.perm_idx)
            check_masks_binary(masks)
    check_choco_invariant(comm)
    if stack:
        check_rate_in_container(target.wire, prev_comm)
