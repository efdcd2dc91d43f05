"""Declarative dynamics setup: one config object from CLI to mixer.

:class:`DynamicsConfig` is the dynamics twin of ``CompressionConfig`` —
everything the trainer needs to build a time-varying consensus operator:
which :class:`~repro.dynamics.schedule.TopologySchedule`, which faults,
the local-update period H and whether gradient tracking is on.
:func:`build_dynamic_mixer` assembles the mixer stack
(schedule → faults → [compression] → [local updates]) for the dense
simulation lowering; the gossip lowering is built explicitly via
``repro.comm.make_gossip_mixer(..., schedule=...)`` (it needs a mesh).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.comm.compressors import CompressionConfig
from repro.comm.mixers import make_dense_mixer, make_hub_mixer
from repro.comm.protocol import Mixer
from repro.dynamics.faults import FaultConfig
from repro.dynamics.local import LocalUpdateMixer
from repro.dynamics.schedule import make_schedule

TOPOLOGY_KINDS = ("static", "round_robin", "dropout", "geometric", "hub")


@dataclasses.dataclass(frozen=True)
class DynamicsConfig:
    """Dynamic-graph training knobs, threaded from CLI to the mixer stack.

    Attributes:
      topology: "static" | "round_robin" | "dropout" | "geometric" —
        the per-round topology process (``repro.dynamics.schedule``) —
        or "hub": the federated hub-and-spoke lowering (every consensus
        round is the exact server average, W = 11ᵀ/K; with
        ``local_updates`` H > 1 this is FedAvg, and adding
        ``gradient_tracking`` yields the SCAFFOLD control variate).
        "hub" has no fault/schedule model yet, so it rejects ``faults``.
      drop_p: link dropout probability for topology="dropout".
      radius: connection radius for topology="geometric" re-draws.
      local_updates: H — optimizer steps per consensus round (H > 1 = local
        SGD between mixes).
      gradient_tracking: carry the drift correction of
        :class:`~repro.dynamics.local.LocalUpdateMixer` (needs an
        uncompressed wire; 2× consensus bytes).
      faults: optional :class:`~repro.dynamics.faults.FaultConfig`
        (stragglers / correlated outages / extra link dropout) composed on
        top of the schedule.
      ef_rebase_every: B — re-base period of the error-feedback compressed
        *gossip* lowering (``make_gossip_mixer(..., schedule=...)``):
        every B-th consensus round exchanges full-precision public copies to
        rebuild the incremental ``hat_mix`` cache under the current W.
        0 = never re-base (only valid for a static fault-free topology, or
        with an adaptive threshold below).
        The dense EF lowering ignores it (it re-mixes full public copies
        every round, so its cache never goes stale).
      ef_rebase_threshold: adaptive re-base: when > 0, the EF gossip
        lowering measures the cache drift ‖s − W_r θ̂‖_F each round and
        re-bases the round it exceeds this threshold, replacing the fixed
        B clock.  0 = use the clock.
      seed: schedule PRNG seed (fault noise has its own seed in
        ``FaultConfig``).
    """

    topology: str = "static"
    drop_p: float = 0.0
    radius: float = 0.5
    local_updates: int = 1
    gradient_tracking: bool = False
    faults: FaultConfig | None = None
    ef_rebase_every: int = 8
    ef_rebase_threshold: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.topology not in TOPOLOGY_KINDS:
            raise ValueError(
                f"unknown topology {self.topology!r}; options: "
                f"{TOPOLOGY_KINDS}")
        if self.local_updates < 1:
            raise ValueError("local_updates (H) must be >= 1")
        if self.ef_rebase_every < 0:
            raise ValueError("ef_rebase_every (B) must be >= 0")
        if self.ef_rebase_threshold < 0:
            raise ValueError("ef_rebase_threshold must be >= 0")
        if self.topology == "dropout" and not 0.0 <= self.drop_p < 1.0:
            raise ValueError("drop_p must be in [0, 1)")
        if (self.topology == "hub" and self.faults is not None
                and self.faults.enabled):
            raise ValueError(
                "topology='hub' (federated server averaging) has no "
                "fault/schedule model yet — the star topology is static "
                "(ROADMAP: federated faults); drop faults or pick a "
                "decentralized topology")
        if self.drop_p > 0 and self.topology != "dropout":
            # a sweep over --drop-p without --topology dropout must fail
            # loudly, not silently train p identical static baselines
            raise ValueError(
                f"drop_p={self.drop_p} has no effect with topology="
                f"{self.topology!r}; pass topology='dropout' (or use "
                "FaultConfig.link_drop_p to compose dropout with another "
                "schedule)")

    @property
    def enabled(self) -> bool:
        """False when the config describes today's static synchronous run."""
        return (self.topology != "static"
                or self.local_updates > 1
                or self.gradient_tracking
                or (self.faults is not None and self.faults.enabled))


def build_dynamic_mixer(cfg: DynamicsConfig, w: np.ndarray,
                        compression: CompressionConfig | None = None
                        ) -> Mixer:
    """Assemble the dense-lowering mixer stack for a dynamics config.

    ``w`` is the base doubly-stochastic matrix (e.g. Metropolis weights of
    the configured graph); topology="geometric" ignores its weights and
    keeps only K, and topology="hub" (federated) keeps only K as well —
    the star W = 11ᵀ/K replaces the graph entirely.
    """
    k = int(np.asarray(w).shape[0])
    if cfg.topology == "hub":
        mixer = make_hub_mixer(k, compression)
    else:
        schedule = make_schedule(
            cfg.topology, w=w, k=k, drop_p=cfg.drop_p, radius=cfg.radius,
            seed=cfg.seed)
        mixer = make_dense_mixer(None, compression=compression,
                                 schedule=schedule, faults=cfg.faults)
    if cfg.local_updates > 1 or cfg.gradient_tracking:
        # under the hub: FedAvg, and with gradient_tracking the tracker
        # correction under W = 11^T/K is exactly SCAFFOLD's control variate
        mixer = LocalUpdateMixer(mixer, cfg.local_updates,
                                 gradient_tracking=cfg.gradient_tracking)
    return mixer
