"""Jitted wrapper: apply the fused gossip update across a parameter pytree.

``gossip_update_tree`` flattens each leaf to 1-D and runs the Pallas kernel
(or the jnp ref on the CPU, see :func:`repro.kernels.kernel_path`), so the
whole pytree update is a single fused pass per leaf instead of 7
elementwise HLO ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import kernel_path
from repro.kernels.gossip_update.kernel import gossip_update
from repro.kernels.gossip_update.ref import gossip_update_ref


@functools.partial(jax.jit, static_argnames=("eta", "interpret", "use_kernel"))
def gossip_update_flat(theta, grad, neighbors, weights, scale, *, eta: float,
                       interpret: bool = False, use_kernel: bool = True):
    path = kernel_path(use_kernel, interpret)
    if path != "ref":
        return gossip_update(theta, grad, neighbors, weights, scale, eta=eta,
                             interpret=path == "interpret")
    return gossip_update_ref(theta, grad, neighbors, weights, scale, eta=eta)


def gossip_update_tree(theta_tree, grad_tree, neighbor_trees, weights, scale,
                       *, eta: float, interpret: bool = False,
                       use_kernel: bool = True):
    """Apply the fused update leaf-wise.

    ``neighbor_trees`` is a list of pytrees (one per neighbor) matching
    ``theta_tree``; ``weights`` is (N+1,) with the self weight first.
    """
    leaves, treedef = jax.tree.flatten(theta_tree)
    grads = treedef.flatten_up_to(grad_tree)
    nbrs = [treedef.flatten_up_to(t) for t in neighbor_trees]
    out = []
    for i, (th, g) in enumerate(zip(leaves, grads)):
        shape = th.shape
        nb = jnp.stack([n[i].reshape(-1) for n in nbrs]) if nbrs else (
            jnp.zeros((0, th.size), th.dtype))
        res = gossip_update_flat(
            th.reshape(-1), g.reshape(-1), nb, weights,
            jnp.asarray(scale, jnp.float32), eta=eta, interpret=interpret,
            use_kernel=use_kernel)
        out.append(res.reshape(shape))
    return jax.tree.unflatten(treedef, out)
