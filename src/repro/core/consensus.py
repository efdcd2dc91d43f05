"""Consensus (mixing) operators: θ ← θ·W lowered four ways for TPU.

All operators act on *node-stacked* pytrees: every leaf has a leading axis K
(the decentralized node count).  Numerically they all implement the same
doubly-stochastic mixing; they differ in the collectives XLA emits.  The
four factories — ``make_dense_mixer`` (einsum; an all-gather under pjit),
``make_gossip_mixer`` (one ``lax.ppermute`` per graph matching),
``make_hub_mixer`` (the exact server average) and ``make_identity_mixer``
(no communication) — are defined in :mod:`repro.comm.mixers` and
re-exported here; each builds a Topology × Transport × Wire stack behind
:class:`repro.comm.composed.ComposedMixer`.  This module adds
:class:`RepeatMixer`.

Protocol v2: every factory returns a :class:`repro.comm.protocol.Mixer`
with ONE calling convention, compressed or not::

    comm  = mixer.init_state(params)               # CommState
    theta, comm = mixer(theta, comm, round=step)   # one consensus round

Uncompressed mixers carry the *trivial* ``CommState`` (no public copies, a
never-consumed PRNG key) and stamp their static full-precision
``wire_bits`` into it each round; ``mixer.state_specs(param_specs)`` gives
matching PartitionSpecs for pjit.  Every factory accepts a ``compression:
CompressionConfig`` (``repro.comm``): when enabled the stack's wire gossips
error-feedback-corrected compressed innovations instead of raw parameters
— same protocol, richer state.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.comm.mixers import (
    make_dense_mixer,
    make_gossip_mixer,
    make_hub_mixer,
    make_identity_mixer,
)
from repro.comm.protocol import Mixer

__all__ = [
    "RepeatMixer", "repeat_mixer",
    "make_dense_mixer", "make_gossip_mixer", "make_hub_mixer",
    "make_identity_mixer",
]


class RepeatMixer(Mixer):
    """θ ← θ·W^rounds: multiple gossip rounds per optimizer step.

    Theorem 1's consensus term contracts like ρ^rounds, so m rounds on a
    sparse graph can substitute for a denser graph at m× the mixing wire —
    a knob for trading interconnect bytes against the convergence constant.
    """

    def __init__(self, mixer: Mixer, rounds: int):
        if rounds < 1:
            raise ValueError("rounds must be >= 1")
        self.inner = mixer
        self.rounds = rounds

    @property
    def compression(self):
        return self.inner.compression

    @property
    def traced_wire(self) -> bool:
        return self.inner.traced_wire

    def init_state(self, params):
        return self.inner.init_state(params)

    def state_specs(self, param_specs):
        return self.inner.state_specs(param_specs)

    def __call__(self, theta, state, *, round=None):
        total_bits = jnp.float32(0.0)
        for _ in range(self.rounds):
            theta, state = self.inner(theta, state, round=round)
            total_bits = total_bits + state.wire_bits
        # wire_bits is per-*step* accounting: sum the inner rounds
        return theta, state._replace(wire_bits=total_bits)

    def bytes_per_round(self, params) -> int:
        return self.rounds * self.inner.bytes_per_round(params)

    def wire_dtype_bytes(self, params):
        inner = self.inner.wire_dtype_bytes(params)
        if inner is None:
            return None
        # the python loop unrolls: the HLO carries `rounds` copies of the
        # inner round's collectives
        return {dt: self.rounds * b for dt, b in inner.items()}


def repeat_mixer(mixer: Mixer, rounds: int) -> Mixer:
    return RepeatMixer(mixer, rounds)
