"""Production meshes for the multi-pod dry-run.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the 512 placeholder
host devices are requested by dryrun.py's XLA_FLAGS before any jax import.

Mesh geometry (TPU v5e):
  single-pod: (16, 16)     axes ("data", "model")   = 256 chips
  multi-pod:  (2, 16, 16)  axes ("pod", "data", "model") = 512 chips

For decentralized training the graph-node axis is "data" (single-pod, K=16)
or ("pod", "data") (multi-pod, K=32): gossip neighbor exchanges over the
"pod" boundary ride the slow DCN links, which is exactly where DR-DSGD's
sparse communication pattern pays off (DESIGN.md §3).
"""

from __future__ import annotations

import jax
import numpy as np


def make_auto_mesh(axis_shapes, axis_names, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (``make_mesh`` defaults to
    ``Explicit`` axes, whose sharding-in-types rules the shard_map mixers
    and the pjit rules here do not follow)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         (jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def node_axes(mesh: jax.sharding.Mesh):
    """Mesh axes carrying the decentralized node dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_nodes(mesh: jax.sharding.Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in node_axes(mesh)]))


def data_axes(mesh: jax.sharding.Mesh):
    """Axes used for batch sharding in serving mode."""
    return node_axes(mesh)


def make_debug_mesh(data: int = 4, model: int = 2) -> jax.sharding.Mesh:
    """Small host mesh for unit tests (requires >= data*model host devices)."""
    return make_auto_mesh((data, model), ("data", "model"))
