"""Jitted public wrapper for the flash-attention kernel.

``flash_attention`` accepts the model's (B, S, KV, G, hd) layout and
dispatches to the Pallas kernel (TPU), the interpret-mode kernel, or the jnp
reference, as :func:`repro.kernels.kernel_path` decides.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import kernel_path
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret", "use_kernel"),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    use_kernel: bool = True):
    """q: (B,H,S,hd); k,v: (B,KVH,T,hd) -> (B,H,S,hd)."""
    path = kernel_path(use_kernel, interpret)
    if path != "ref":
        return flash_attention_fwd(
            q, k, v, causal=causal, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, interpret=path == "interpret")
    return attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
