"""Public wrapper for the Mamba selective-scan kernel, with the reference's
VJP as its backward."""

from __future__ import annotations

import contextlib
import functools

import jax

from repro.kernels import kernel_path
from repro.kernels.mamba_scan import kernel
from repro.kernels.mamba_scan.ref import selective_scan_ref

# One traced and lowered kernel for every call of a shape: a program calls
# it once a Mamba layer, and lowering the kernel is most of a program's
# lowering time.
_kernel_scan = jax.jit(kernel.selective_scan, static_argnames=("interpret",))
# the open records of :func:`traced_paths`
_records: list[set[str]] = []


def scan_path(seq_len: int, *, interpret: bool = False) -> str:
    """How :func:`selective_scan` runs ``seq_len`` tokens: one token (the
    decode step) is a single plain step of the reference, anything longer
    takes :func:`repro.kernels.kernel_path`."""
    if seq_len <= 1:
        return "ref"
    return kernel_path(True, interpret)


@contextlib.contextmanager
def traced_paths():
    """While open, collects the paths (:func:`scan_path`) that
    :func:`selective_scan` takes as a program is traced: the program's own
    record of how its scans run."""
    paths: set[str] = set()
    _records.append(paths)
    try:
        yield paths
    finally:
        _records.remove(paths)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _scan(u, dt, b, c, a, d, h0, interpret):
    return _kernel_scan(u, dt, b, c, a, d, h0, interpret=interpret)


def _scan_fwd(u, dt, b, c, a, d, h0, interpret):
    return (_scan(u, dt, b, c, a, d, h0, interpret),
            (u, dt, b, c, a, d, h0))


def _scan_bwd(interpret, res, g):
    # recompute through the reference: exact, and no kernel of its own
    _, vjp = jax.vjp(selective_scan_ref, *res)
    return vjp(g)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, dt, b, c, a, d, h0, *, interpret: bool = False):
    """u, dt: (B,S,di); b, c: (B,S,N); a: (di,N); d: (di,); h0: (B,di,N)
    -> (y (B,S,di), hT (B,di,N)), y holding the ``d`` skip."""
    path = scan_path(u.shape[1], interpret=interpret)
    for paths in _records:
        paths.add(path)
    if path == "ref":
        return selective_scan_ref(u, dt, b, c, a, d, h0)
    return _scan(u, dt, b, c, a, d, h0, path == "interpret")
