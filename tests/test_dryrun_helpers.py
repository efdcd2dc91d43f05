"""Dry-run plumbing: input specs, cache specs, mesh helpers, shape skips.

(The actual 256/512-device lowering runs via `python -m repro.launch.dryrun`;
these tests cover the pure helpers on the single CPU device.)
"""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.launch.mesh import make_auto_mesh
from repro.models import SHAPES, TransformerLM, input_shapes
from repro.models.transformer import input_specs


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k"])
def test_input_specs_shapes(arch, shape):
    cfg = get_arch(arch)
    sc = SHAPES[shape]
    specs = input_specs(cfg, sc, num_nodes=16)
    for leaf in jax.tree.leaves(specs):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    if sc.kind == "train":
        toks = specs["tokens"]
        assert toks.shape[0] == 16                       # node axis
        assert toks.shape[0] * toks.shape[1] == sc.global_batch
        prefix = cfg.frontend_len if cfg.frontend != "token" else 0
        assert toks.shape[2] == sc.seq_len - prefix + 1  # +1 for labels
        if prefix:
            assert specs["embeddings"].shape == (
                16, sc.global_batch // 16, prefix, cfg.d_model)
    elif sc.kind == "prefill":
        total = sum(
            specs[k].shape[1] for k in ("tokens", "embeddings") if k in specs)
        assert total == sc.seq_len
    else:
        assert specs["token"].shape == (sc.global_batch, 1)
        assert specs["pos"].shape == ()


def test_input_specs_is_the_public_name():
    assert input_specs is input_shapes


def test_long_500k_skip_policy():
    from repro.launch.dryrun import runs_shape

    runs = {a: runs_shape(get_arch(a), SHAPES["long_500k"]) for a in ARCH_IDS}
    assert runs["h2o_danube_1_8b"]      # SWA-only => sub-quadratic
    assert runs["rwkv6_7b"]             # ssm
    assert runs["jamba_1_5_large_398b"]  # hybrid
    for a in ("grok_1_314b", "pixtral_12b", "qwen2_0_5b", "gemma2_27b",
              "llama3_405b", "musicgen_medium", "deepseek_moe_16b"):
        assert not runs[a], a
    # every arch runs the other three shapes
    for a in ARCH_IDS:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert runs_shape(get_arch(a), SHAPES[s])


def test_cache_pspecs_structure_matches_cache():
    cfg = get_arch("jamba_1_5_large_398b", smoke=True)
    model = TransformerLM(cfg)
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    cache = jax.eval_shape(lambda: model.init_cache(4, 64))
    specs = model.cache_pspecs(4, 64, mesh, "data")
    assert jax.tree.structure(cache) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P))


def test_batch1_cache_shards_sequence():
    cfg = get_arch("h2o_danube_1_8b")
    model = TransformerLM(cfg)

    class FakeMesh:  # cache_pspecs only reads .shape
        shape = {"data": 16, "model": 16}

    # batch=1 (long_500k): batch axis unshardable -> sequence axis gets data
    specs = model.cache_pspecs(1, 4096, FakeMesh(), "data")
    kv = specs["groups"]["l0"]["k"]
    assert kv[1] is None          # stacked layer axis
    assert kv[2] == "data"        # ring-buffer sequence axis sharded


def test_mesh_helpers():
    from repro.launch.mesh import node_axes

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    assert node_axes(FakeMesh()) == ("pod", "data")

    class SingleMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    assert node_axes(SingleMesh()) == ("data",)
    from repro.launch.mesh import num_nodes

    assert num_nodes(FakeMesh()) == 32
    assert num_nodes(SingleMesh()) == 16
