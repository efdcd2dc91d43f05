"""Transport layer: how a round of public copies moves between nodes.

One of the three composable consensus layers (see ``comm/composed.py``).
A transport owns the *lowering structure* — mesh, matching decomposition,
partition specs, einsum dtype — and the pure full-precision application;
the round bodies in ``ComposedMixer`` read this structure and thread the
wire codec through it.

:class:`DenseTransport`  — einsum over the leading node axis.  Simple,
                           works anywhere (CPU simulation with any K);
                           under pjit it lowers to an all-gather of
                           O(K·P) bytes.  The paper-faithful baseline.
:class:`GossipTransport` — shard_map + one ``lax.ppermute`` per matching
                           of the edge-colored graph: O(deg·P) bytes,
                           matchings of a ring/torus map onto physical
                           TPU interconnect links.  Requires
                           K == prod(mesh node axes).  With
                           ``replica_axis`` set, a psum-mean over the
                           inner replica axis runs before the gossip
                           round (hierarchical: FSDP-inside /
                           gossip-across).  ``incremental=True``: the
                           receiver keeps a running mix cache, so EF
                           wires own ``hat_mix`` here.
:class:`StarTransport`   — hub-and-spoke: every node uploads its block to
                           a (virtual) server and downloads the exact
                           mean — the federated server-averaging round,
                           simulated as a node-axis mean.  Wire model:
                           2K × per-node payload (up + down).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.mixing import MixingDecomposition

AxisName = str | tuple[str, ...]


def _bcast(v: jax.Array, like: jax.Array) -> jax.Array:
    """Reshape a (k_local,) weight vector to broadcast over a (k_local, ...) leaf."""
    return v.reshape(v.shape + (1,) * (like.ndim - 1))


def gossip_mix_local(theta_local, self_w, match_ws, perms, axis: AxisName):
    """The per-shard body of the gossip transport (must run inside shard_map).

    Args:
      theta_local: pytree of (k_local, ...) local node blocks.
      self_w: (k_local,) diagonal weights for the local nodes.
      match_ws: list of (k_local,) per-matching edge weights.
      perms: list of ppermute (src, dst) pair lists (static python).
      axis: mesh axis name(s) carrying the node dimension.

    Wire compression is not an ad-hoc dtype cast here: compressed payloads
    ride the codec wires of ``repro.comm.wire`` through ``ComposedMixer``.
    """

    def leaf(x):
        acc = x.astype(jnp.float32) * _bcast(self_w, x)
        for pw, perm in zip(match_ws, perms):
            recv = jax.lax.ppermute(x, axis, perm)
            acc = acc + recv.astype(jnp.float32) * _bcast(pw, x)
        return acc.astype(x.dtype)

    return jax.tree.map(leaf, theta_local)


class Transport:
    """Lowering-structure base.  ``incremental`` marks transports whose
    receivers keep a running mix cache (EF wires then own ``hat_mix``)."""

    incremental = False


class DenseTransport(Transport):
    """θ_i ← Σ_j W_ij θ_j via einsum along the leading node axis."""

    def __init__(self, compute_dtype=jnp.float32):
        self.compute_dtype = compute_dtype

    def apply_w(self, w, theta):
        """One full-precision dense mixing round under a given W (static
        pre-cast or traced per-round f32)."""
        def leaf(x):
            out = jnp.einsum(
                "kl,l...->k...", w, x.astype(self.compute_dtype),
                precision=jax.lax.Precision.HIGHEST,
            )
            return out.astype(x.dtype)

        return jax.tree.map(leaf, theta)


class StarTransport(Transport):
    """Hub-and-spoke server averaging, simulated as an exact node mean.

    Every consensus round each node uploads its parameter block and
    downloads the global average — the federated lowering of the ROADMAP's
    decentralized↔federated axis.  ``apply`` is the ``W = 11^T/K`` product
    computed as a mean (cheaper than the einsum, same fixed point).
    """

    def __init__(self, k: int):
        if k < 1:
            raise ValueError(f"star transport needs k >= 1, got {k}")
        self.k = int(k)

    def apply(self, theta):
        def leaf(x):
            xf = x.astype(jnp.float32)
            avg = jnp.mean(xf, axis=0, keepdims=True)
            return jnp.broadcast_to(avg, xf.shape).astype(x.dtype)

        return jax.tree.map(leaf, theta)


class GossipTransport(Transport):
    """shard_map/ppermute structure over the matching decomposition.

    ``param_specs`` is a pytree of PartitionSpecs matching the
    *node-stacked* params (leading dim partitioned over ``node_axis``);
    it feeds shard_map in/out specs so tensor-parallel dims stay sharded.
    Holds the frozen f32 decomposition weights (``self_w``/``match_ws``) —
    what the static stacks mix with, bit-identical to the pre-refactor
    mixers — plus the static edge coloring ``perm_idx`` the dynamic
    stacks gather per-round weights through.
    """

    incremental = True

    def __init__(self, decomp: MixingDecomposition, mesh: jax.sharding.Mesh,
                 node_axis: AxisName, param_specs,
                 replica_axis: str | None = None):
        axes = (node_axis,) if isinstance(node_axis, str) else tuple(node_axis)
        k_mesh = int(np.prod([mesh.shape[a] for a in axes]))
        k = decomp.self_weights.shape[0]
        if k != k_mesh:
            raise ValueError(
                f"gossip mixer needs K == mesh node size: K={k}, "
                f"mesh {axes}={k_mesh}")
        self.k = k
        self.mesh = mesh
        self.axis: AxisName = (node_axis if isinstance(node_axis, str)
                               else tuple(node_axis))
        self.param_specs = param_specs
        self.replica_axis = replica_axis
        self.decomp = decomp
        self.self_w = jnp.asarray(decomp.self_weights, jnp.float32)
        self.match_ws = [jnp.asarray(w, jnp.float32)
                         for w in decomp.matching_weights]
        self.perms = decomp.ppermute_pairs()
        self.perm_idx = [np.asarray(p, np.int64) for p in decomp.matchings]
        self.p_node = jax.sharding.PartitionSpec(self.axis)

    def node_index(self):
        """Global node id of this shard (traced; inside shard_map only)."""
        if isinstance(self.axis, str):
            return jax.lax.axis_index(self.axis)
        idx = jax.lax.axis_index(self.axis[0])
        for a in self.axis[1:]:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx
