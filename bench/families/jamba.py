"""The Jamba family (AI21 Jamba, Jamba2): Mamba-1 mixers and attention
layers in one period, every layer with a GLU MLP.

Layer ``i`` is attention iff ``i % attn_layer_period == attn_layer_offset``
(HF ``JambaConfig.layers_block_type``), Mamba otherwise.  Attention has no
positional encoding; each Mamba mixer RMS-norms dt, B and C after
``x_proj``.  Only ``num_experts`` 1 is taken (no layer has experts).

Parameter layout (the program's ``TransformerLM`` with one scanned group of
``P = attn_layer_period`` layers, stacked ``G = layers / P`` times)::

    embedding.table (V, D)           final_norm.scale (D,)
    lm_head.table (V, D)             only when the embeddings are not tied
    groups.l<i>.norm1.scale (G, D)   groups.l<i>.norm2.scale (G, D)
    groups.l<i>.ffn.w_gate (G, D, F) groups.l<i>.ffn.w_up (G, D, F)
    groups.l<i>.ffn.w_down (G, F, D)
    attention layers:
    groups.l<i>.mix.wq (G, D, H, hd) groups.l<i>.mix.wk (G, D, KV, hd)
    groups.l<i>.mix.wv (G, D, KV, hd) groups.l<i>.mix.wo (G, H, hd, D)
    Mamba layers (di = expand * D, R = dt_rank, N = d_state, K = d_conv):
    groups.l<i>.mix.in_proj (G, D, 2 di)   groups.l<i>.mix.out_proj (G, di, D)
    groups.l<i>.mix.conv_w (G, di, K)      groups.l<i>.mix.conv_b (G, di)
    groups.l<i>.mix.x_proj (G, di, R + 2N) groups.l<i>.mix.dt_proj (G, R, di)
    groups.l<i>.mix.dt_bias (G, di)        groups.l<i>.mix.a_log (G, di, N)
    groups.l<i>.mix.d_skip (G, di)         groups.l<i>.mix.dt_norm (G, R)
    groups.l<i>.mix.b_norm (G, N)          groups.l<i>.mix.c_norm (G, N)

Seeded values: matrices N(0, 1/fan_in) (the convolution's fan-in is its
width K); norm scales, the norms of dt, B and C and the skip ``d_skip``
1 + N(0, 0.1^2); ``a_log`` N(0, 1), so A = -exp(a_log) < 0 spreads the
channels' decay over long and short memories; biases N(0, 0.02^2) and
``dt_bias`` N(0, 0.1^2).  No parameter sits at a value where a wrong use
of it would go unseen.

Costs, from the shapes alone:

* ``matmul_params`` -- the weights of every matrix product a token goes
  through: attention's and the Mamba mixers' projections (``in_proj``,
  ``x_proj``, ``dt_proj``, ``out_proj``), every MLP and the head (a tied
  table counts once, as the head).
* The selective scan, per token and Mamba layer (:func:`scan_cost`): 7 di N
  operations (exp(dt A): 2, (dt u) B: 1, the state's multiply-add: 2, the
  read-out against C: 2) and (3 di + 2N) 4 bytes (u, dt, B and C in, y out,
  float32), plus the di N float32 state in and out once per request.  The
  causal convolution: 2 di K operations per token and layer.
* Training counts 6 operations per matmul parameter per token, causal
  attention as the dense family counts it, and 3 times the scan's and
  convolution's forward operations.  Recomputation does not count.
* A decode step of ``n`` active requests: ``2 * matmul_params`` per
  request, ``4 * H * hd`` per attended position per attention layer and the
  scan's and convolution's operations of one token per request and Mamba
  layer; its bytes are every parameter read once (``itemsize`` 4, as the
  dense family counts them), the live keys and values, and the recurrent
  rows of the active requests (state di N and convolution window
  (K - 1) di, float32) read and written.
"""

from __future__ import annotations

from bench.families import param_count


def arch_config(cfg: dict):
    from repro.models.config import ArchConfig

    if cfg.get("torch_dtype") != "float32":
        raise ValueError("the program stores float32 parameters only")
    required = {"num_experts": 1, "mamba_conv_bias": True,
                "mamba_proj_bias": False, "sliding_window": None,
                "hidden_act": "silu"}
    for key, want in required.items():
        if cfg.get(key) != want:
            raise ValueError(f"the program's Jamba takes {key}={want!r} only, "
                             f"not {cfg.get(key)!r}")
    L, D, H, KV, hd, F, V = _dims(cfg)
    return ArchConfig(
        name=cfg["name"], arch_type="hybrid", n_layers=L, d_model=D,
        n_heads=H, n_kv_heads=KV, head_dim=hd, d_ff=F, vocab=V,
        layer_pattern=tuple(_kinds(cfg)[:cfg["attn_layer_period"]]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings")),
        rope_theta=None, rmsnorm_eps=float(cfg["rms_norm_eps"]),
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"])


def reference():
    from bench.reference import jamba

    return jamba


# -- layout -------------------------------------------------------------------------

def _dims(cfg: dict) -> tuple:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"],
            cfg.get("head_dim") or d // h, cfg["intermediate_size"],
            cfg["vocab_size"])


def _mamba_dims(cfg: dict) -> tuple:
    """(d_inner, dt_rank, d_state, d_conv)."""
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_dt_rank"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"])


def _kinds(cfg: dict) -> list[str]:
    """Each layer's mixer, ``attn`` or ``mamba``."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attn" if i % period == offset else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def attention_layers(cfg: dict) -> int:
    return _kinds(cfg).count("attn")


def mamba_layers(cfg: dict) -> int:
    return _kinds(cfg).count("mamba")


def shapes(cfg: dict) -> dict:
    L, D, H, KV, hd, F, V = _dims(cfg)
    di, R, N, K = _mamba_dims(cfg)
    period = cfg["attn_layer_period"]
    if L % period:
        raise ValueError(f"{L} layers are no whole number of {period}-layer periods")
    G = L // period
    s = {"embedding.table": (V, D), "final_norm.scale": (D,)}
    for i, kind in enumerate(_kinds(cfg)[:period]):
        p = f"groups.l{i}."
        s.update({p + "norm1.scale": (G, D), p + "norm2.scale": (G, D),
                  p + "ffn.w_gate": (G, D, F), p + "ffn.w_up": (G, D, F),
                  p + "ffn.w_down": (G, F, D)})
        if kind == "attn":
            s.update({p + "mix.wq": (G, D, H, hd), p + "mix.wk": (G, D, KV, hd),
                      p + "mix.wv": (G, D, KV, hd), p + "mix.wo": (G, H, hd, D)})
            continue
        s.update({p + "mix.in_proj": (G, D, 2 * di), p + "mix.conv_w": (G, di, K),
                  p + "mix.conv_b": (G, di), p + "mix.x_proj": (G, di, R + 2 * N),
                  p + "mix.dt_proj": (G, R, di), p + "mix.dt_bias": (G, di),
                  p + "mix.a_log": (G, di, N), p + "mix.d_skip": (G, di),
                  p + "mix.out_proj": (G, di, D), p + "mix.dt_norm": (G, R),
                  p + "mix.b_norm": (G, N), p + "mix.c_norm": (G, N)})
    if not cfg.get("tie_word_embeddings"):
        s["lm_head.table"] = (V, D)
    return s


def init(path: str, shape: tuple) -> tuple[str, float]:
    leaf = path.rsplit(".", 1)[-1]
    if leaf in ("scale", "dt_norm", "b_norm", "c_norm", "d_skip"):
        return "norm", 0.1
    if leaf == "a_log":
        return "normal", 1.0
    if leaf == "dt_bias":
        return "normal", 0.1
    if leaf == "conv_b":
        return "normal", 0.02
    if leaf == "conv_w":                               # (G, di, K)
        return "normal", 1.0 / shape[-1] ** 0.5
    if leaf in ("wq", "wk", "wv"):                     # (G, D, heads, hd)
        return "normal", 1.0 / shape[-3] ** 0.5
    if leaf == "wo":                                   # (G, H, hd, D)
        return "normal", 1.0 / (shape[-3] * shape[-2]) ** 0.5
    if leaf == "table":
        return "normal", 1.0 / shape[-1] ** 0.5
    return "normal", 1.0 / shape[-2] ** 0.5            # (G, fan_in, fan_out)


# -- costs --------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    L, D, H, KV, hd, F, V = _dims(cfg)
    di, R, N, _ = _mamba_dims(cfg)
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    mamba = D * 2 * di + di * (R + 2 * N) + R * di + di * D
    return (attention_layers(cfg) * attn + mamba_layers(cfg) * mamba
            + L * 3 * D * F + V * D)


def _attn_flops_per_position(cfg: dict) -> int:
    """Forward operations of one query against one key, summed over layers."""
    _, _, H, _, hd, _, _ = _dims(cfg)
    return 4 * H * hd * attention_layers(cfg)


def _recurrent_flops_per_token(cfg: dict) -> int:
    """Forward operations of the scan and the convolution of one token,
    summed over the Mamba layers."""
    di, _, N, K = _mamba_dims(cfg)
    return mamba_layers(cfg) * (7 * di * N + 2 * di * K)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attended = (seq_len + 1) / 2.0
    return (6.0 * matmul_params(cfg)
            + 3.0 * _attn_flops_per_position(cfg) * attended
            + 3.0 * _recurrent_flops_per_token(cfg))


def kv_bytes_per_token(cfg: dict, itemsize: int = 4) -> int:
    _, _, _, KV, hd, _, _ = _dims(cfg)
    return 2 * attention_layers(cfg) * KV * hd * itemsize


def recurrent_bytes_per_request(cfg: dict) -> int:
    """The float32 recurrent rows of one request: each Mamba layer's state
    and convolution window."""
    di, _, N, K = _mamba_dims(cfg)
    return mamba_layers(cfg) * (di * N + (K - 1) * di) * 4


def decode_step_cost(cfg: dict, active: int, kv_tokens: int,
                     itemsize: int = 4) -> tuple[float, float]:
    """(operations, bytes) of one decode step with ``active`` requests whose
    contexts hold ``kv_tokens`` tokens in all (the new ones included)."""
    _, D, _, _, _, _, V = _dims(cfg)
    flops = (2.0 * matmul_params(cfg) * active
             + _attn_flops_per_position(cfg) * kv_tokens
             + _recurrent_flops_per_token(cfg) * active)
    # every weight but the embedding rows that are gathered, read once
    weights = param_count(cfg) - (0 if cfg.get("tie_word_embeddings") else V * D)
    nbytes = (weights * itemsize + kv_bytes_per_token(cfg, itemsize) * kv_tokens
              + 2 * recurrent_bytes_per_request(cfg) * active)
    return flops, float(nbytes)


def scan_cost(cfg: dict, tokens: int) -> tuple[float, float]:
    """(operations, bytes) of the selective scans of one request's prefill
    of ``tokens`` prompt tokens, over every Mamba layer."""
    di, _, N, _ = _mamba_dims(cfg)
    n = mamba_layers(cfg)
    ops = 7.0 * di * N * tokens * n
    nbytes = (4.0 * (3 * di + 2 * N) * tokens + 2 * 4.0 * di * N) * n
    return ops, nbytes
