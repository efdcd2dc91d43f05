"""Time-varying topology schedules: a traced mixing matrix per round.

A :class:`TopologySchedule` maps the (traced) round counter to the round's
doubly-stochastic (K, K) mixing matrix ``W_r`` **as a traced operand** — the
matrix rides into the compiled train step as data, never as program
structure, so a run over a time-varying graph compiles exactly one program
(mirroring the traced-rate codec design of ``repro.comm.schedule``).

The schedule's W is the single source of truth for *both* consensus
lowerings: the dense mixer einsums it directly, and the gossip mixer gathers
per-matching edge weights out of it along the static edge-coloring of the
*union support* (see ``repro.comm.topology.gather_round_vectors``), so the
two lowerings see bit-identical weights each round.

Implementations:

* :class:`StaticSchedule`      — constant W; reproduces today's frozen
  Dense/Gossip mixers bit-exactly (the regression anchor).
* :class:`RoundRobinSchedule`  — round r runs only matching ``r % M`` of the
  edge coloring (``permutation_decomposition``): one neighbor exchange per
  round, the classical matching-based gossip of wireless schedules.
* :class:`DropoutSchedule`     — iid Bernoulli link dropout at rate ``p``
  on a static base graph, renormalized on device
  (:func:`~repro.graphs.mixing.renormalize_masked_weights`); ``p = 0`` is
  bit-identical to :class:`StaticSchedule`.
* :class:`GeometricRedrawSchedule` — nodes re-draw positions on the unit
  square every round and connect within ``radius``; Metropolis weights are
  re-derived on device (:func:`~repro.graphs.mixing.metropolis_weights_traced`).
  Support changes every round, so only the dense lowering can run it.

Randomness is a pure function of the round counter
(``fold_in(PRNGKey(seed), round)``), so a restored checkpoint replays the
identical topology sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.mixing import (
    MixingDecomposition,
    metropolis_weights_traced,
    permutation_decomposition,
    renormalize_masked_weights,
    symmetric_uniform,
)


class TopologySchedule:
    """Protocol: per-round traced mixing matrix.

    Attributes:
      k: node count.
      static_support: True when supp(W_r) ⊆ supp(base W) for every round —
        the condition for the gossip lowering (static ppermute structure,
        traced weights).  Schedules whose support moves (geometric re-draws)
        are dense-only.
      seed: seed of the schedule's own randomness (dropout coins, re-draws).
      constant: True when every round's W is the same matrix (only
        :class:`StaticSchedule`) — what lets EF gossip skip the re-base.
    """

    k: int
    static_support = True
    seed = 0
    constant = False

    def round_weights(self, rounds) -> jax.Array:
        """The (K, K) doubly-stochastic W of round ``rounds`` (traced)."""
        raise NotImplementedError

    def base_weights(self) -> np.ndarray:
        """A static W whose support contains every round's support (used to
        build the gossip decomposition and for static byte estimates)."""
        raise NotImplementedError

    def decomposition(self) -> MixingDecomposition:
        """Edge coloring of the union support (gossip lowering structure)."""
        if not self.static_support:
            raise ValueError(
                f"{type(self).__name__} re-draws its support every round; "
                "only the dense lowering can run it")
        return permutation_decomposition(self.base_weights())

    def _round_key(self, rounds) -> jax.Array:
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), rounds)


class StaticSchedule(TopologySchedule):
    """Constant topology — the frozen-graph baseline as a schedule."""

    constant = True

    def __init__(self, w: np.ndarray):
        self._w_np = np.asarray(w, np.float64)
        self.w = jnp.asarray(self._w_np, jnp.float32)
        self.k = int(self.w.shape[0])

    def round_weights(self, rounds) -> jax.Array:
        return self.w

    def base_weights(self) -> np.ndarray:
        return self._w_np


class RoundRobinSchedule(TopologySchedule):
    """One matching of the edge coloring per round, cycled round-robin.

    Round r exchanges only along matching ``r % M``; the matched pairs keep
    their base pairwise weight and return the unmatched mass to the
    diagonal, so each W_r is doubly stochastic and the cycle's product
    contracts like the full W (B-connectivity over M rounds).
    """

    def __init__(self, w: np.ndarray):
        self._w_np = np.asarray(w, np.float64)
        self.k = int(self._w_np.shape[0])
        decomp = permutation_decomposition(self._w_np)
        self._decomp = decomp
        mats = []
        for perm, pw in zip(decomp.matchings, decomp.matching_weights):
            m = np.zeros((self.k, self.k), np.float64)
            for i in range(self.k):
                j = int(perm[i])
                if j != i:
                    m[i, j] = pw[i]
            np.fill_diagonal(m, 1.0 - m.sum(axis=1))
            mats.append(m)
        # (M, K, K) static stack; per-round selection is a traced gather
        self._stack = jnp.asarray(np.stack(mats), jnp.float32)

    @property
    def num_matchings(self) -> int:
        return int(self._stack.shape[0])

    def round_weights(self, rounds) -> jax.Array:
        return self._stack[rounds % self._stack.shape[0]]

    def base_weights(self) -> np.ndarray:
        return self._w_np

    def decomposition(self) -> MixingDecomposition:
        return self._decomp


class DropoutSchedule(TopologySchedule):
    """Bernoulli link dropout on a static base W, renormalized on device.

    Every link of the base graph fails independently with probability ``p``
    each round; the dropped weight returns to the incident diagonals
    (doubly-stochastic by construction).  ``p = 0`` reproduces the static
    schedule bit-exactly — the coins multiply weights by exactly 1.0.
    """

    def __init__(self, w: np.ndarray, p: float, seed: int = 0):
        if not 0.0 <= p < 1.0:
            raise ValueError(f"dropout p must be in [0, 1), got {p}")
        self._w_np = np.asarray(w, np.float64)
        self.w = jnp.asarray(self._w_np, jnp.float32)
        self.k = int(self.w.shape[0])
        self.p = float(p)
        self.seed = seed

    def round_weights(self, rounds) -> jax.Array:
        if self.p == 0.0:
            return self.w
        u = symmetric_uniform(self._round_key(rounds), self.k)
        keep = (u >= self.p).astype(jnp.float32)
        return renormalize_masked_weights(self.w, keep)

    def base_weights(self) -> np.ndarray:
        return self._w_np


class GeometricRedrawSchedule(TopologySchedule):
    """Random geometric graph re-drawn every round (mobile/wireless nodes).

    Each round the K nodes take fresh uniform positions on the unit square
    and connect within ``radius``; Metropolis weights are derived on device.
    Rounds may be disconnected — consensus relies on connectivity *over
    time* (B-connectivity), which holds w.h.p. for radius above the
    connectivity threshold.  Dense lowering only (the support moves).
    """

    static_support = False

    def __init__(self, k: int, radius: float = 0.5, seed: int = 0):
        if k < 2:
            raise ValueError("need K >= 2 nodes")
        if not 0.0 < radius <= np.sqrt(2.0):
            raise ValueError(f"radius must be in (0, sqrt(2)], got {radius}")
        self.k = int(k)
        self.radius = float(radius)
        self.seed = seed

    def round_weights(self, rounds) -> jax.Array:
        pts = jax.random.uniform(self._round_key(rounds), (self.k, 2),
                                 jnp.float32)
        d2 = jnp.sum(jnp.square(pts[:, None, :] - pts[None, :, :]), axis=-1)
        adj = (d2 < self.radius ** 2).astype(jnp.float32)
        adj = adj * (1.0 - jnp.eye(self.k, dtype=jnp.float32))
        return metropolis_weights_traced(adj)

    def base_weights(self) -> np.ndarray:
        raise ValueError("geometric re-draw has no static base support")


def make_schedule(kind: str, *, w: np.ndarray | None = None,
                  k: int | None = None, drop_p: float = 0.0,
                  radius: float = 0.5, seed: int = 0) -> TopologySchedule:
    """Build a schedule by name (the ``--topology`` CLI entry point)."""
    if kind == "static":
        return StaticSchedule(w)
    if kind == "round_robin":
        return RoundRobinSchedule(w)
    if kind == "dropout":
        sched = DropoutSchedule(w, drop_p, seed=seed)
        return sched
    if kind == "geometric":
        return GeometricRedrawSchedule(k if k is not None else w.shape[0],
                                       radius=radius, seed=seed)
    raise ValueError(f"unknown topology schedule {kind!r}; options: "
                     "static, round_robin, dropout, geometric")
