"""Per-assigned-architecture smoke tests (reduced same-family variants).

For each of the 10 assigned architectures: instantiate the smoke config
(2-4 layers, d_model <= 512, <= 4 experts), run one forward pass + one
DR-DSGD train step on CPU, and one decode step — asserting output shapes and
the absence of NaNs. The FULL configs are exercised only via the dry-run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.core import RobustConfig, TrainStepConfig, build_train_step, \
    make_dense_mixer
from repro.core.drdsgd import init_state, replicate_params
from repro.graphs import metropolis_weights, ring_graph
from repro.models import TransformerLM
from repro.optim import sgd


def _batch(cfg, k, b, s, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (k, b, s + 1), 0,
                              cfg.vocab)
    batch = {"tokens": toks}
    if cfg.frontend != "token":
        batch["embeddings"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), (k, b, cfg.frontend_len, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_arch(arch, smoke=True)
    assert cfg.d_model <= 512
    assert cfg.moe is None or cfg.moe.num_experts <= 4
    model = TransformerLM(cfg)
    k, b, s = 4, 2, 32

    # forward: per-sample logits
    params = model.init(jax.random.PRNGKey(0))
    single = {kk: v[0] for kk, v in _batch(cfg, k, b, s).items()}
    logits = model.logits_all(params, {"tokens": single["tokens"][:, :s],
                                       **({"embeddings": single["embeddings"]}
                                          if cfg.frontend != "token" else {})})
    assert logits.shape == (b, s, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch

    # one decentralized DR-DSGD train step over a ring of 4 nodes
    w = metropolis_weights(ring_graph(k))
    step = build_train_step(
        model.loss, sgd(1e-2), make_dense_mixer(w),
        TrainStepConfig(robust=RobustConfig(mu=6.0)))
    state = init_state(replicate_params(params, k), sgd(1e-2))
    new_state, metrics = jax.jit(step)(state, _batch(cfg, k, b, s))
    assert int(new_state.step) == 1
    for key in ("loss_mean", "loss_worst", "robust_objective"):
        assert np.isfinite(float(metrics[key])), (arch, key)
    # params changed and are finite
    moved = 0.0
    for old, new in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(new_state.params)):
        assert bool(jnp.isfinite(new).all()), arch
        moved += float(jnp.sum(jnp.abs(new - old)))
    assert moved > 0.0, arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_arch(arch, smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, cache_len = 2, 16
    cache = model.init_cache(b, cache_len)
    tok = jnp.zeros((b, 1), jnp.int32)
    logits, new_cache = jax.jit(model.decode_step)(
        params, tok, jnp.int32(0), cache)
    assert logits.shape == (b, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_exact_assignment(arch):
    """Pin the full configs to the assigned hyperparameters."""
    cfg = get_arch(arch)
    expected = {
        "h2o_danube_1_8b": (24, 2560, 32, 8, 6912, 32000),
        "rwkv6_7b": (32, 4096, 64, 64, 14336, 65536),
        "grok_1_314b": (64, 6144, 48, 8, 32768, 131072),
        "jamba_1_5_large_398b": (72, 8192, 64, 8, 24576, 65536),
        "pixtral_12b": (40, 5120, 32, 8, 14336, 131072),
        "qwen2_0_5b": (24, 896, 14, 2, 4864, 151936),
        "gemma2_27b": (46, 4608, 32, 16, 36864, 256000),
        "llama3_405b": (126, 16384, 128, 8, 53248, 128256),
        "musicgen_medium": (48, 1536, 24, 24, 6144, 2048),
        "deepseek_moe_16b": (28, 2048, 16, 16, 1408, 102400),
        "jamba2_3b": (28, 2560, 20, 1, 8192, 65536),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab)
    assert got == expected, (arch, got, expected)
    moe_expect = {
        "grok_1_314b": (8, 2, 0),
        "jamba_1_5_large_398b": (16, 2, 0),
        "deepseek_moe_16b": (64, 6, 2),
    }
    if arch in moe_expect:
        assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.num_shared) == \
            moe_expect[arch]
    else:
        assert cfg.moe is None
