"""jamba2-3b [hybrid]: Mamba-1 and attention layers 13:1, dense MLPs.

28L, d_model=2560, 20 heads (GQA kv=1, head_dim 128), d_ff=8192,
vocab=65536, tied head.  Layer i is attention iff i % 14 == 7 (HF
``JambaConfig`` ``attn_layer_period`` / ``attn_layer_offset``), so the
scanned period is 14 layers, 7 and 21 attention.  Jamba's attention has no
positional encoding; its Mamba mixers (expand 2, d_state 16, d_conv 4,
dt_rank 160) RMS-norm dt, B and C after ``x_proj``.  ``num_experts`` is 1:
every layer has a GLU MLP.
[https://huggingface.co/ai21labs/AI21-Jamba2-3B; arXiv:2403.19887]
"""

from repro.models.config import ArchConfig

_PERIOD, _OFFSET = 14, 7
_PATTERN = tuple("attn" if i == _OFFSET else "mamba" for i in range(_PERIOD))


def full() -> ArchConfig:
    return ArchConfig(
        name="jamba2-3b",
        arch_type="hybrid",
        n_layers=28,
        d_model=2560,
        n_heads=20,
        n_kv_heads=1,
        head_dim=128,
        d_ff=8192,
        vocab=65536,
        layer_pattern=_PATTERN,
        rope_theta=None,
        rmsnorm_eps=1e-6,
        tie_embeddings=True,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=160,
    )


def smoke() -> ArchConfig:
    return ArchConfig(
        name="jamba2-smoke",
        arch_type="hybrid",
        n_layers=4,            # one period: attention at position 2
        d_model=128,
        n_heads=8,
        n_kv_heads=1,
        d_ff=256,
        vocab=512,
        layer_pattern=("mamba", "mamba", "attn", "mamba"),
        rope_theta=None,
        tie_embeddings=True,
        mamba_d_state=8,
        mamba_d_conv=4,
        mamba_expand=2,
        attn_q_chunk=32,
        attn_kv_chunk=32,
        logits_chunk=64,
    )
