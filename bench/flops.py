"""Operations and bytes that the algorithm needs, from a configuration's
shapes alone: the same work whatever implements it.

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

from bench.weights import dims, shapes


def matmul_params(cfg: dict) -> int:
    m = dims(cfg)
    L, D, H, KV, hd, F, V = (m[k] for k in ("L", "D", "H", "KV", "hd", "F", "V"))
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return L * per_layer + V * D


def param_count(cfg: dict) -> int:
    n = 0
    for shape in shapes(cfg).values():
        size = 1
        for s in shape:
            size *= s
        n += size
    return n


def attn_flops_per_position(cfg: dict) -> int:
    """Forward operations of one query against one key, summed over layers."""
    m = dims(cfg)
    return 4 * m["H"] * m["hd"] * m["L"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attended = (seq_len + 1) / 2.0
    return 6.0 * matmul_params(cfg) + 3.0 * attn_flops_per_position(cfg) * attended


def kv_bytes_per_token(cfg: dict, itemsize: int = 4) -> int:
    m = dims(cfg)
    return 2 * m["L"] * m["KV"] * m["hd"] * itemsize


def decode_step_cost(cfg: dict, active: int, kv_tokens: int,
                     itemsize: int = 4) -> tuple[float, float]:
    """(operations, bytes) of one decode step with ``active`` requests whose
    contexts hold ``kv_tokens`` tokens in all (the new ones included)."""
    m = dims(cfg)
    flops = 2.0 * matmul_params(cfg) * active + attn_flops_per_position(cfg) * kv_tokens
    # every weight but the embedding rows that are gathered, read once
    weights = param_count(cfg) - (0 if cfg.get("tie_word_embeddings")
                                  else m["V"] * m["D"])
    nbytes = weights * itemsize + kv_bytes_per_token(cfg, itemsize) * kv_tokens
    return flops, float(nbytes)
