"""Seeded random weights for a configuration file, made on the device in one
jitted call, in the parameter layout the configuration's family
(``bench/families/<family>.py``) gives: its ``shapes`` and, leaf by leaf,
its ``init`` rule.  The benchmark hands the same tree to the program and,
made anew from the seed, to the plain reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split(".")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "."))
        else:
            out[p] = v
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (all 64 bits count)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make_params(cfg: dict, seed: int, dtype=jnp.float32, device=None):
    """The parameter tree of ``cfg`` for ``seed``, made on the device."""
    fam = families.load(cfg)
    spec = fam.shapes(cfg)
    names = sorted(spec)

    def make(key):
        keys = jax.random.split(key, len(names))
        flat = {}
        for k, name in zip(keys, names):
            shape = spec[name]
            kind, std = fam.init(name, shape)
            x = jax.random.normal(k, shape, jnp.float32) * std
            flat[name] = (x + 1.0 if kind == "norm" else x).astype(dtype)
        return nest(flat)

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(make)(key)
