"""Where JAX's persistent compilation cache lives.

A full-width step program takes tens of seconds to compile on a TPU, and a
run finds an earlier run's programs only in the directory they were written
to, so the directory must not move between runs.
``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (which reads it
itself); otherwise the cache goes to ``.jax_cache`` at the root of the
checkout, which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root: src/repro/utils/compile_cache.py -> three levels up
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
