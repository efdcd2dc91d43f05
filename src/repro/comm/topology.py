"""Topology layer: the per-round mixing matrix ``W(round)``.

One of the three composable consensus layers (see ``comm/composed.py``):

* **Topology** (this module) answers *who talks to whom with what weight
  this round* — a ``round_w(rounds) -> (K, K)`` provider plus the static
  base support needed by gossip lowerings and wire accounting.
* **Transport** (``comm/transport.py``) answers *how the payloads move*.
* **Wire** (``comm/wire.py``) answers *what crosses each link*.

Three providers cover the shipped matrix:

:class:`StaticTopology`     — a fixed doubly-stochastic W (ring, ER, ...).
:class:`ScheduledTopology`  — a :class:`~repro.dynamics.schedule
                              .TopologySchedule` composed with optional
                              :class:`~repro.dynamics.faults.FaultConfig`
                              replay (link drops / stragglers / outages
                              renormalized back to doubly-stochastic).
:class:`StarTopology`       — hub-and-spoke: ``W = 11^T / K``, the exact
                              server average of federated optimization
                              (DRFA-style when stacked under
                              ``LocalUpdateMixer``).

``round_w`` is traced: a scheduled topology changes the round's W without
changing the compiled program (the one-program-per-config invariant).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def active_links(w) -> jnp.ndarray:
    """Traced count of directed links with nonzero weight this round."""
    k = w.shape[0]
    off = 1.0 - jnp.eye(k, dtype=jnp.float32)
    return jnp.sum((w > 0).astype(jnp.float32) * off)


def gather_round_vectors(w, perm_idx):
    """(self_w, [match_w], [mask]) gathered from a traced round matrix W_r.

    ``perm_idx`` is the static edge coloring of the union support (one (K,)
    involution per matching); the per-matching edge weights and {0, 1} link
    masks are gathered out of W_r, so a dropped/faulted link carries weight
    0 and mask 0 without the ppermute structure ever changing.  Shared by
    the plain/memoryless and error-feedback dynamic gossip stacks — the
    single source of per-round wire truth.
    """
    k = w.shape[0]
    arange = np.arange(k)
    self_w = jnp.diagonal(w)
    match_ws, masks = [], []
    for pidx in perm_idx:
        active = pidx != arange
        pw = jnp.where(active, w[arange, pidx], 0.0)
        match_ws.append(pw)
        masks.append((pw > 0).astype(jnp.float32))
    return self_w, match_ws, masks


def active_sends(masks) -> jnp.ndarray:
    """Traced count of active directed matching links (wire accounting)."""
    sends = jnp.float32(0.0)
    for m in masks:
        sends = sends + jnp.sum(m)
    return sends


class Topology:
    """Per-round mixing-weight provider.

    ``time_varying`` is a *class-level* contract, not a per-instance
    observation: a :class:`ScheduledTopology` over a ``StaticSchedule`` is
    still time-varying (its W is a traced operand), which is what keeps
    every dynamic mixer config in ONE compiled program.
    """

    time_varying: bool = False
    k: int

    def round_w(self, rounds) -> jnp.ndarray:
        """The (K, K) doubly-stochastic W of round ``rounds`` (traced)."""
        raise NotImplementedError

    def base_weights(self) -> np.ndarray:
        """Host-side base support: the union of every round's nonzeros.

        Used for gossip matching decomposition and static wire accounting.
        Raises ``ValueError`` when the support is not statically known
        (e.g. geometric redraw) — callers fall back to complete support.
        """
        raise NotImplementedError


class StaticTopology(Topology):
    """A fixed graph: ``round_w`` is constant."""

    time_varying = False

    def __init__(self, w):
        self._w_np = np.asarray(w, np.float64)
        if self._w_np.ndim != 2 or self._w_np.shape[0] != self._w_np.shape[1]:
            raise ValueError(f"W must be square, got {self._w_np.shape}")
        self.k = int(self._w_np.shape[0])
        self.w = jnp.asarray(self._w_np, jnp.float32)

    def round_w(self, rounds) -> jnp.ndarray:
        return self.w

    def base_weights(self) -> np.ndarray:
        return self._w_np


class ScheduledTopology(Topology):
    """``TopologySchedule`` composed with optional fault replay.

    The faults are a pure function of the round index
    (``fault_keep_matrix(cfg, rounds, k)``), so a restored checkpoint
    replays the identical keep-mask sequence; the masked W is renormalized
    back to doubly-stochastic on device.
    """

    time_varying = True

    def __init__(self, schedule, faults=None):
        self.schedule = schedule
        self.faults = faults if (faults is not None and faults.enabled) \
            else None
        self.k = schedule.k

    def round_w(self, rounds) -> jnp.ndarray:
        from repro.dynamics.faults import fault_keep_matrix
        from repro.graphs.mixing import renormalize_masked_weights

        w = self.schedule.round_weights(rounds)
        if self.faults is not None:
            keep, _ = fault_keep_matrix(self.faults, rounds, self.k)
            w = renormalize_masked_weights(w, keep)
        return w

    def base_weights(self) -> np.ndarray:
        return self.schedule.base_weights()


class StarTopology(Topology):
    """Hub-and-spoke: every consensus round is the exact global average.

    ``W = 11^T / K`` — the server-averaging step of federated optimization,
    lowered as a topology so the whole federated stack reuses the dense /
    star transports unchanged.  Spectrally this is the rho=0 endpoint of
    the paper's mixing-rate axis: one round reaches consensus exactly.
    """

    time_varying = False

    def __init__(self, k: int):
        if k < 1:
            raise ValueError(f"hub topology needs k >= 1, got {k}")
        self.k = int(k)
        self._w_np = np.full((self.k, self.k), 1.0 / self.k, np.float64)
        self.w = jnp.asarray(self._w_np, jnp.float32)

    def round_w(self, rounds) -> jnp.ndarray:
        return self.w

    def base_weights(self) -> np.ndarray:
        return self._w_np
