"""Dynamic-graph simulation: time-varying topologies, faults, local updates.

DR-DSGD's setting — decentralized learning over graphs — lives on links
that appear and drop (wireless/edge), nodes that straggle, and rounds where
communication is too expensive to run every step.  This subsystem makes all
three first-class while keeping the compiled-program discipline of the rest
of the repo: **the topology of every round is a traced operand**, so a
dropout sweep, a fault storm, or a round-robin matching cycle runs in ONE
compiled program per configuration (no recompiles across rounds — asserted
by ``benchmarks/fig9_dynamics.py`` via jit cache stats).

Layout:

schedule.py — :class:`TopologySchedule`: per-round doubly-stochastic W as a
              traced (K, K) operand.  static / round_robin (one edge-colored
              matching per round) / dropout (Bernoulli links, on-device
              renormalization) / geometric (fresh random-geometric graph
              each round, on-device Metropolis weights).
faults.py   — :class:`FaultConfig`: link dropout, per-round node stragglers,
              correlated multi-round outages — all as a symmetric link-keep
              mask renormalized into W (doubly-stochastic preserved).
local.py    — :class:`LocalUpdateMixer`: H local steps per consensus round
              with optional gradient-tracking correction carried in
              ``CommState.track``.
config.py   — :class:`DynamicsConfig` + :func:`build_dynamic_mixer`: the
              declarative entry point used by ``TrainerSpec``
              (``--topology/--drop-p/--local-updates/...`` CLI flags).
              ``--topology hub`` selects the federated lowering
              (``repro.comm.make_hub_mixer`` — exact server average;
              FedAvg under ``--local-updates H``, SCAFFOLD with
              ``--gradient-tracking``); hub has no fault model yet, so
              hub + faults raises at config build.

The consensus lowerings themselves are the factories of
``repro.comm.mixers``: ``make_dense_mixer(None, schedule=s, faults=f)``
(einsum, any schedule) and ``make_gossip_mixer(None, mesh, ...,
schedule=s, faults=f)`` (static matchings + traced weights/masks; the
memoryless masked int8/int4 Pallas wire, or error feedback with the
``hat_mix`` re-base clock of ``repro.comm.wire.RebaseClock``).

Conventions — how H, dropout p and the EF step size γ interact:

* ``CommState.rounds`` is the dynamics clock.  Unwrapped mixers tick it per
  consensus round; under :class:`LocalUpdateMixer` it ticks per *optimizer
  step* (the wrapper owns the clock), so with period H rounds
  ``H-1, 2H-1, ...`` are consensus rounds and everything keyed off the
  counter (topology coins, fault windows, compression-schedule anneals)
  advances on the step clock.
* Topology and fault randomness are pure functions of the round index
  (``fold_in(PRNGKey(seed), round)``): restoring a checkpoint replays the
  identical graph/fault sequence, and dense vs gossip lowerings draw
  bit-identical coins.
* Dropout shrinks the per-round spectral gap (the effective contraction is
  that of E[W_r], see ``tests/test_dynamics.py``); combining heavy dropout
  with EF compression therefore tolerates less γ — keep
  ``CompressionConfig.gamma`` at or below the static recommendation, and
  prefer larger H over larger p when budgeting the same expected wire.
* Wire accounting is per active directed link × per-node payload (traced
  ``wire_bits``): straggler/outage rounds with no live links report exactly
  0 comm bytes; gradient tracking doubles consensus-round bytes.
* The EF gossip wire keeps a SECOND clock, ``CommState.ef_rounds``: it
  counts consensus rounds the compressed wire actually executed (wrappers
  overwrite ``rounds`` with the step clock) and fires the full-precision
  ``hat_mix`` re-base every ``ef_rebase_every``-th tick.  Delta rounds bill
  the codec payload on active links, re-base rounds bill f32 — the
  amortized wire is ((B−1)·codec + f32)/B per link per round.
"""

from repro.dynamics.config import (
    TOPOLOGY_KINDS,
    DynamicsConfig,
    build_dynamic_mixer,
)
from repro.dynamics.faults import FaultConfig, fault_keep_matrix, replay_fault_masks
from repro.dynamics.local import LocalUpdateMixer
from repro.dynamics.schedule import (
    DropoutSchedule,
    GeometricRedrawSchedule,
    RoundRobinSchedule,
    StaticSchedule,
    TopologySchedule,
    make_schedule,
)

__all__ = [
    "DynamicsConfig", "TOPOLOGY_KINDS", "build_dynamic_mixer",
    "FaultConfig", "fault_keep_matrix", "replay_fault_masks",
    "LocalUpdateMixer",
    "TopologySchedule", "StaticSchedule", "RoundRobinSchedule",
    "DropoutSchedule", "GeometricRedrawSchedule", "make_schedule",
]
