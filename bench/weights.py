"""Seeded random weights for a configuration file, made on the device in one
jitted call, in the parameter layout the serving and training stack reads:

    embedding.table (V, D)           final_norm.scale (D,)
    lm_head.table (V, D)             only when the embeddings are not tied
    groups.l0.norm1.scale (L, D)     groups.l0.norm2.scale (L, D)
    groups.l0.mix.wq (L, D, H, hd)   groups.l0.mix.bq (L, H, hd)   with bias
    groups.l0.mix.wk (L, D, KV, hd)  groups.l0.mix.bk (L, KV, hd)
    groups.l0.mix.wv (L, D, KV, hd)  groups.l0.mix.bv (L, KV, hd)
    groups.l0.mix.wo (L, H, hd, D)
    groups.l0.ffn.w_gate (L, D, F)   groups.l0.ffn.w_up (L, D, F)
    groups.l0.ffn.w_down (L, F, D)

Matrices are N(0, 1/fan_in); norm scales are 1 + N(0, 0.1^2) and biases
N(0, 0.02^2), so that no parameter is at a value where a wrong use of it
would go unseen.  The benchmark hands the same tree to the program and, made
anew from the seed, to the plain reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], D=d, H=h,
                KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                F=cfg["intermediate_size"], V=cfg["vocab_size"])


def shapes(cfg: dict) -> dict:
    """{path: shape} of every parameter."""
    m = dims(cfg)
    L, D, H, KV, hd, F, V = (m[k] for k in ("L", "D", "H", "KV", "hd", "F", "V"))
    s = {
        "embedding.table": (V, D),
        "final_norm.scale": (D,),
        "groups.l0.norm1.scale": (L, D),
        "groups.l0.norm2.scale": (L, D),
        "groups.l0.mix.wq": (L, D, H, hd),
        "groups.l0.mix.wk": (L, D, KV, hd),
        "groups.l0.mix.wv": (L, D, KV, hd),
        "groups.l0.mix.wo": (L, H, hd, D),
        "groups.l0.ffn.w_gate": (L, D, F),
        "groups.l0.ffn.w_up": (L, D, F),
        "groups.l0.ffn.w_down": (L, F, D),
    }
    if cfg.get("attention_bias"):
        s.update({"groups.l0.mix.bq": (L, H, hd),
                  "groups.l0.mix.bk": (L, KV, hd),
                  "groups.l0.mix.bv": (L, KV, hd)})
    if not cfg.get("tie_word_embeddings"):
        s["lm_head.table"] = (V, D)
    return s


def _std(path: str, shape: tuple) -> tuple[str, float]:
    leaf = path.rsplit(".", 1)[-1]
    if leaf == "scale":
        return "norm", 0.1
    if leaf in ("bq", "bk", "bv"):
        return "normal", 0.02
    if leaf in ("wq", "wk", "wv"):                     # (L, D, heads, hd)
        return "normal", 1.0 / np.sqrt(shape[-3])
    if leaf == "wo":                                   # (L, H, hd, D)
        return "normal", 1.0 / np.sqrt(shape[-3] * shape[-2])
    if leaf == "table":
        return "normal", 1.0 / np.sqrt(shape[-1])
    return "normal", 1.0 / np.sqrt(shape[-2])


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
    spec = shapes(cfg)
    names = sorted(spec)

    def make(key):
        keys = jax.random.split(key, len(names))
        flat = {}
        for k, name in zip(keys, names):
            shape = spec[name]
            kind, std = _std(name, shape)
            x = jax.random.normal(k, shape, jnp.float32) * std
            flat[name] = (x + 1.0 if kind == "norm" else x).astype(dtype)
        return nest(flat)

    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(make)(key)
