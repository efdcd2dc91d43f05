"""Plain reference of a DR-DSGD step (Alg. 2 of arXiv:2208.13810) with the
job's settings from its traffic file.  It imports nothing of the system
under test.

For each node i, with its own parameters theta_i and batch:

    l_i, g_i = loss and gradient of the family's reference model
    g_i     <- g_i * min(1, clip / (|g_i| + 1e-12))      global-norm clip
    s_i      = exp(min(l_i, loss_clip) / mu) / mu        the DR reweighting
    theta_i <- theta_i - lr * s_i * g_i                  SGD
    theta_i <- sum_j W_ij theta_j                        mixing

W is the Metropolis matrix of the job's graph: W_ij = 1 / (1 + max(d_i,
d_j)) on an edge, W_ii = 1 - sum of the row's other entries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


def graph_edges(kind: str, k: int) -> set:
    if kind == "complete":
        return {(i, j) for i in range(k) for j in range(k) if i != j}
    if kind == "ring":
        return {(i, (i + d) % k) for i in range(k) for d in (1, -1)
                if (i + d) % k != i}
    raise ValueError(f"graph {kind!r} has no reference")


def metropolis(kind: str, k: int) -> np.ndarray:
    edges = graph_edges(kind, k)
    deg = [sum(1 for (a, _) in edges if a == i) for i in range(k)]
    w = np.zeros((k, k))
    for i, j in edges:
        w[i, j] = 1.0 / (1 + max(deg[i], deg[j]))
    w[np.diag_indices(k)] = 1.0 - w.sum(axis=1)
    return w


def node_grad(cfg: dict, job: dict, params, rows, dtype=jnp.float32):
    """(loss, clipped gradient, DR scale) of one node's batch (B, L + 1)."""
    ref = families.load(cfg).reference()
    l, g = jax.value_and_grad(
        lambda p: ref.batch_loss(cfg, p, rows, dtype))(params)
    leaves = jax.tree.leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, job["grad_clip"] / (norm + 1e-12)
                                               ).astype(x.dtype), g)
    scale = jnp.exp(jnp.minimum(l, job["loss_clip"]) / job["mu"]) / job["mu"]
    return l, g, scale
