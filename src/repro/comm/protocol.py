"""Consensus protocol v2: ONE calling convention for every mixer.

Every consensus operator — identity, dense einsum, ppermute gossip,
hierarchical, compressed, repeated — is a :class:`Mixer` with the uniform
stateful signature

    theta', comm' = mixer(theta, comm, round=step)

where ``comm`` is a :class:`CommState` allocated by ``mixer.init_state(params)``
and shardable via ``mixer.state_specs(param_specs)``.  There is no second
"plain ``theta -> theta``" convention and no ``stateful`` attribute to branch
on: uncompressed mixers simply carry a *trivial* state (``hat``/``hat_mix``
empty, a PRNG key they never consume) and stamp their static full-precision
``wire_bits`` into it every round, so the train step, the ``lax.scan`` driver,
and every metric read one shape of state regardless of the wire codec.

:class:`CommMetrics` is the per-round accounting view of a ``CommState``
(``wire_bits``, ``res_norm``, ``rounds``) that ``build_train_step`` surfaces
uniformly in the metrics dict.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class CommMetrics(NamedTuple):
    """Per-round communication accounting, uniform across all mixers.

    wire_bits: f32 — wire bits injected by the last consensus round (static
               full-precision bits for uncompressed mixers, traced rate-aware
               bits under a compression schedule).
    res_norm:  f32 — error-feedback innovation norm ‖θ − θ̂‖ offered to the
               codec on the last round (0 for uncompressed mixers).
    rounds:    int32 — consensus rounds completed.
    """

    wire_bits: jax.Array
    res_norm: jax.Array
    rounds: jax.Array


class CommState(NamedTuple):
    """Per-node consensus state threaded through the train loop.

    hat:      public copies θ̂ (float32, same structure/shape as params); the
              error-feedback residual is θ − θ̂.  () for uncompressed mixers
              and for the memoryless (error_feedback=False) ablation.
    hat_mix:  running s_i = Σ_j W_ij θ̂_j (compressed gossip lowering only,
              EF mode; () otherwise) so each round only adds the received
              innovations.
    key:      PRNG key for stochastic rounding / random sparsification
              (carried but never consumed by uncompressed mixers).
    res_norm: f32 — innovation norm ‖θ − θ̂‖_F (over all nodes and leaves)
              offered to the codec on the last round; 0 before the first
              round, in memoryless mode, and for uncompressed mixers.
              Drives adaptive schedules and the ``ef_residual_norm`` metric.
    res_ref:  f32 — post-warmup reference norm latched by an adaptive
              schedule (0 until latched / for other schedule kinds).
    rounds:   int32 — consensus rounds completed.
    wire_bits: f32 — wire bits injected by the last round (all senders,
              rate-aware under a schedule; static bits for uncompressed
              mixers).
    track:    dynamics state carried across rounds by wrapper mixers
              (``repro.dynamics``): the gradient-tracking correction and
              window anchor of ``LocalUpdateMixer`` live here.  () for every
              plain mixer.  Inner mixers must treat it as opaque — wrappers
              re-attach it after delegating (see LocalUpdateMixer).
    ef_rounds: the error-feedback *consensus-round* clock of the dynamic
              compressed gossip lowering (int32): counts rounds the EF wire
              actually executed and drives the periodic ``hat_mix`` re-base
              (``repro.comm.wire.RebaseClock``, rebase when
              ``ef_rounds % B == B − 1``).  Deliberately distinct from
              ``rounds``, which wrapper mixers (``LocalUpdateMixer``)
              overwrite with the optimizer-step clock — the re-base cadence
              must follow executed consensus rounds, not steps.  () for
              every other mixer (and in pre-PR5 checkpoints, which restore
              padded — see ``repro.checkpoint.restore_train_state``).
    ef_drift: f32 — the measured staleness ‖s − W_r θ̂‖_F of the incremental
              ``hat_mix`` cache, maintained only by the *adaptive* re-base
              mode of the clocked EF gossip wire
              (``ef_rebase_threshold > 0``): each round measures the drift
              against the current topology and the next round re-bases when
              it exceeds the threshold.  () for every other mixer and for
              the fixed-clock re-base mode (and in older checkpoints, which
              restore padded).
    """

    hat: Any
    hat_mix: Any
    key: jax.Array
    res_norm: jax.Array
    res_ref: jax.Array
    rounds: jax.Array
    wire_bits: jax.Array
    track: Any = ()
    ef_rounds: Any = ()
    ef_drift: Any = ()

    @property
    def metrics(self) -> CommMetrics:
        """The accounting view surfaced per step by ``build_train_step``."""
        return CommMetrics(wire_bits=self.wire_bits, res_norm=self.res_norm,
                           rounds=self.rounds)


def trivial_comm_state(seed: int = 0) -> CommState:
    """The uncompressed mixers' state: accounting fields only."""
    return CommState(
        hat=(), hat_mix=(),
        key=jax.random.PRNGKey(seed),
        res_norm=jnp.float32(0.0),
        res_ref=jnp.float32(0.0),
        rounds=jnp.int32(0),
        wire_bits=jnp.float32(0.0),
    )


def trivial_state_specs() -> CommState:
    """PartitionSpecs matching :func:`trivial_comm_state` (all replicated)."""
    rep = jax.sharding.PartitionSpec()
    return CommState(hat=(), hat_mix=(), key=rep, res_norm=rep, res_ref=rep,
                     rounds=rep, wire_bits=rep)


class Mixer:
    """Base class of the uniform consensus protocol.

    Subclasses either implement :meth:`_mix` (pure ``theta -> theta`` body;
    the base ``__call__`` handles the state bookkeeping) or override
    :meth:`__call__` outright (the compressed mixers, which consume the PRNG
    key and maintain public copies).

    Class attributes:
      compression: the ``CompressionConfig`` the mixer was built with, or
        None for full-precision mixers (what ``build_train_step`` and the
        trainer validate against — there is no ``stateful`` flag anymore).
      traced_wire: True when a compression schedule makes the per-round wire
        bits a traced quantity; the train step then reports
        ``CommState.wire_bits / 8`` instead of the static
        :meth:`bytes_per_round` estimate.
    """

    compression = None
    traced_wire = False

    @property
    def name(self) -> str:
        """Label of the device scope ``obs:consensus/<name>``: the class
        name, unless the mixer (or its factory) says otherwise."""
        return type(self).__name__

    # -- state ----------------------------------------------------------------

    def init_state(self, params) -> CommState:
        return trivial_comm_state()

    def state_specs(self, param_specs) -> CommState:
        """PartitionSpecs matching :meth:`init_state` (for pjit shardings)."""
        return trivial_state_specs()

    # -- accounting -----------------------------------------------------------

    def bytes_per_round(self, params) -> int:
        """Static estimate of wire bytes one consensus round injects."""
        raise NotImplementedError

    def wire_dtype_bytes(self, params) -> dict[str, float] | None:
        """Per-HLO-dtype bytes one round's collective-permutes physically
        move across the whole graph, or None when the lowering compiles to
        no collectives (the dense/einsum simulation mixers, whose wire is
        accounted only).  This is the contract the jaxpr/HLO auditor
        (``repro.analysis.audit.audit_wire``) cross-checks against the
        compiled program — it may differ from :meth:`bytes_per_round` where
        the accounting is *effective* bits (the int4 rate riding the int8
        container) or amortized (the EF re-base period)."""
        return None

    # -- the protocol ---------------------------------------------------------

    def _mix(self, theta):
        raise NotImplementedError

    def mix_tree(self, tree, state: CommState):
        """Pure consensus application to an arbitrary pytree (no state
        advance, no codec) — used by wrappers that gossip auxiliary
        variables, e.g. the gradient-tracking tracker exchange of
        ``repro.dynamics.LocalUpdateMixer``.  Stateful/compressed mixers do
        not implement this (their wire is entangled with their state)."""
        return self._mix(tree)

    def __call__(self, theta, state: CommState, *, round=None):
        """One consensus round: ``theta', comm' = mixer(theta, comm, round=i)``.

        ``round`` is the (traced) optimizer-step index; the base mixers do
        not consume it, schedule-driven mixers key their rate off their own
        ``CommState.rounds`` counter (which counts *consensus* rounds, a
        different clock under ``mix_every``/``repeat_mixer``).
        """
        with jax.named_scope(f"obs:consensus/{self.name}"):
            mixed = self._mix(theta)
        return mixed, state._replace(
            rounds=state.rounds + 1,
            wire_bits=jnp.float32(8.0 * self.bytes_per_round(theta)),
        )
