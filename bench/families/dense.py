"""The dense decoder family (Qwen2, Mistral type): every layer full or
sliding-window GQA attention and a GLU MLP.

Parameter layout (the program's ``TransformerLM`` with one scanned group)::

    embedding.table (V, D)           final_norm.scale (D,)
    lm_head.table (V, D)             only when the embeddings are not tied
    groups.l0.norm1.scale (L, D)     groups.l0.norm2.scale (L, D)
    groups.l0.mix.wq (L, D, H, hd)   groups.l0.mix.bq (L, H, hd)   with bias
    groups.l0.mix.wk (L, D, KV, hd)  groups.l0.mix.bk (L, KV, hd)
    groups.l0.mix.wv (L, D, KV, hd)  groups.l0.mix.bv (L, KV, hd)
    groups.l0.mix.wo (L, H, hd, D)
    groups.l0.ffn.w_gate (L, D, F)   groups.l0.ffn.w_up (L, D, F)
    groups.l0.ffn.w_down (L, F, D)

Seeded values: matrices N(0, 1/fan_in); norm scales 1 + N(0, 0.1^2) and
biases N(0, 0.02^2), so that no parameter is at a value where a wrong use of
it would go unseen.

Costs, from the shapes alone (the same work whatever implements it):

* ``matmul_params`` -- the weights of every matrix product a token goes
  through: attention and MLP projections of every layer and the head (the
  tied embedding table counts once, as the head).  The embedding gather is
  no matrix product and does not count.
* Training counts 6 operations per matmul parameter per token (forward 2,
  backward 4) and causal attention: per layer and token, ``4 * H * hd``
  per attended position (scores and weighted values), forward, times 3 with
  the backward, over an average of ``(S + 1) / 2`` attended positions.
  Recomputation does not count.
* A decode step of ``n`` active requests counts ``2 * matmul_params`` per
  request and ``4 * H * hd`` per attended position per layer; its bytes are
  every parameter the step reads once (float32) plus the live keys and
  values of the active requests.
"""

from __future__ import annotations

import numpy as np

from bench.families import param_count


def arch_config(cfg: dict):
    from repro.models.config import ArchConfig

    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    window = cfg.get("use_sliding_window", False)
    if cfg.get("torch_dtype") != "float32":
        raise ValueError("the program stores float32 parameters only")
    return ArchConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // h,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        layer_pattern=("swa",) if window else ("attn",),
        sliding_window=cfg["sliding_window"] if window else None,
        qkv_bias=bool(cfg.get("attention_bias")),
        tie_embeddings=bool(cfg.get("tie_word_embeddings")),
        rope_theta=float(cfg["rope_theta"]),
        rmsnorm_eps=float(cfg["rms_norm_eps"]))


def reference():
    from bench.reference import transformer

    return transformer


# -- parameters -------------------------------------------------------------------

def _dims(cfg: dict) -> tuple:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"],
            cfg.get("head_dim") or d // h, cfg["intermediate_size"],
            cfg["vocab_size"])


def shapes(cfg: dict) -> dict:
    L, D, H, KV, hd, F, V = _dims(cfg)
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


def init(path: str, shape: tuple) -> tuple[str, float]:
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


# -- costs --------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    L, D, H, KV, hd, F, V = _dims(cfg)
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return L * per_layer + V * D


def _attn_flops_per_position(cfg: dict) -> int:
    """Forward operations of one query against one key, summed over layers."""
    L, _, H, _, hd, _, _ = _dims(cfg)
    return 4 * H * hd * L


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attended = (seq_len + 1) / 2.0
    return 6.0 * matmul_params(cfg) + 3.0 * _attn_flops_per_position(cfg) * attended


def kv_bytes_per_token(cfg: dict, itemsize: int = 4) -> int:
    L, _, _, KV, hd, _, _ = _dims(cfg)
    return 2 * L * KV * hd * itemsize


def decode_step_cost(cfg: dict, active: int, kv_tokens: int,
                     itemsize: int = 4) -> tuple[float, float]:
    """(operations, bytes) of one decode step with ``active`` requests whose
    contexts hold ``kv_tokens`` tokens in all (the new ones included)."""
    _, D, _, _, _, _, V = _dims(cfg)
    flops = 2.0 * matmul_params(cfg) * active + _attn_flops_per_position(cfg) * kv_tokens
    # every weight but the embedding rows that are gathered, read once
    weights = param_count(cfg) - (0 if cfg.get("tie_word_embeddings") else V * D)
    nbytes = weights * itemsize + kv_bytes_per_token(cfg, itemsize) * kv_tokens
    return flops, float(nbytes)
