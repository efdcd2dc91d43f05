"""Pallas TPU kernels for the framework's compute hot spots.

The paper itself has no kernel-level contribution (it is an optimizer /
communication algorithm), but the production framework around it does:

  flash_attention/  blockwise online-softmax GQA attention
                    (causal, sliding-window, softcap; grid-carried VMEM
                    scratch; MXU-aligned 128x128 blocks)
  gossip_update/    fused DR-DSGD local update + weighted neighbor combine
                    (paper Eq. 9 in one HBM pass)
  rwkv6_scan/       chunked WKV6 recurrence with the state matrix resident
                    in VMEM scratch across time chunks
  mamba_scan/       Mamba-1 selective scan with the state resident in
                    VMEM scratch across time blocks, 1024 channels to a
                    vreg a token; Jamba's prefill runs it (``models/ssm.py``)
  quant_gossip/     fused int8 quantize / dequantize-accumulate for the
                    compressed gossip consensus (repro.comm): per-block
                    absmax scales + stochastic rounding in one pass, so the
                    only wire buffer is the int8 payload

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper) and ref.py (pure-jnp oracle); correctness is swept in
tests/test_kernel_*.py and tests/test_comm.py with interpret=True on CPU,
and tests/test_tpu_compile.py compiles the main-path kernels for a
described v5e.  Every wrapper picks its path with :func:`kernel_path`.
"""

import jax


def kernel_path(use_kernel: bool, interpret: bool) -> str:
    """How a kernel wrapper runs: ``"pallas"``, ``"interpret"`` or ``"ref"``.

    ``use_kernel=False`` asks for the jnp reference and ``interpret=True``
    for the Pallas interpreter, on any backend.  Otherwise the TPU always
    runs the compiled kernel (a kernel that fails to compile raises), and
    only the CPU backend may take the reference in its place; any other
    backend has no path and raises.
    """
    if not use_kernel:
        return "ref"
    if interpret:
        return "interpret"
    backend = jax.default_backend()
    if backend == "tpu":
        return "pallas"
    if backend == "cpu":
        return "ref"
    raise NotImplementedError(
        f"no Pallas kernel path on the {backend!r} backend; pass "
        "use_kernel=False for the jnp reference")
