"""The system under test, as the benchmark builds it from a configuration
file: the one place that maps the file's keys onto the program's own."""

from __future__ import annotations


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


def model(cfg: dict):
    """The program's model, checked to take the benchmark's parameter tree."""
    from repro.models import TransformerLM

    from bench.weights import flatten, shapes

    m = TransformerLM(arch_config(cfg))
    theirs = {k: tuple(v.shape) for k, v in flatten(m.param_shapes()).items()}
    if theirs != shapes(cfg):
        raise ValueError(f"parameter layout differs: program {theirs} vs "
                         f"benchmark {shapes(cfg)}")
    return m


def program_seed(seed: int) -> int:
    """The 31-bit seed the program's own PRNG state takes."""
    return int(seed) % (2 ** 31 - 1)
