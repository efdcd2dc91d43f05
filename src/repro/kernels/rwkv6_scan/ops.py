"""Jitted wrapper for the WKV6 scan kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels import kernel_path
from repro.kernels.rwkv6_scan.kernel import wkv6_scan
from repro.kernels.rwkv6_scan.ref import wkv6_ref


@functools.partial(jax.jit, static_argnames=("block_t", "interpret", "use_kernel"))
def wkv6(r, k, v, w, u, *, block_t: int = 64, interpret: bool = False,
         use_kernel: bool = True):
    """r,k,v,w: (B,H,T,hd); u: (H,hd) -> (B,H,T,hd)."""
    path = kernel_path(use_kernel, interpret)
    if path != "ref":
        return wkv6_scan(r, k, v, w, u, block_t=block_t,
                         interpret=path == "interpret")
    return wkv6_ref(r, k, v, w, u)
