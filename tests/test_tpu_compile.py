"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is described
rather than attached, so these tests run on the CPU backend and catch what
interpret mode cannot: block shapes that break the (8, 128) / (32, 128)
tiling rule, and kernels that need more VMEM than the chip has.  Shapes are
qwen2-0.5b's (``configs/qwen2_0_5b.py``): the embedding and an MLP matrix as
per-node gossip leaves, and the int8 KV rows of the serving pool.

The topology is described inside a module-scoped fixture, never at import,
and the persistent compilation cache is off while these tests compile.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.quant_gossip import kernel as qk

QWEN = get_arch("qwen2_0_5b")
EMBED = QWEN.vocab * QWEN.d_model           # 136,134,656: a multiple of 65536
MLP = QWEN.d_model * QWEN.d_ff              # 4,358,144: a ragged tail
KV_D = QWEN.n_kv_heads * QWEN.resolved_head_dim


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _tiles(k, d, block_d=65536):
    n_blk = qk.num_blocks(d, block_d)
    return qk.tile_shape(k, d, n_blk), n_blk


@pytest.mark.parametrize("k,d", [(1, EMBED), (2, MLP), (1, MLP)])
def test_quantize_compiles_for_v5e(one_chip, k, d):
    fn = functools.partial(qk.quantize_tiles, qmax=127, block_d=65536)
    text = _compiled_text(fn, one_chip, ((k, d), jnp.float32),
                          ((k, d), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,d", [(1, EMBED), (2, MLP)])
def test_dequant_accumulate_compiles_for_v5e(one_chip, k, d):
    tiles, n_blk = _tiles(k, d)
    text = _compiled_text(qk.dequant_accumulate_tiles, one_chip,
                          ((k, d), jnp.float32), (tiles, jnp.int8),
                          ((k, n_blk), jnp.float32), ((k,), jnp.float32))
    assert "tpu_custom_call" in text


def test_masked_quantize_compiles_for_v5e(one_chip):
    def fn(x, u, mask):
        return qk.quantize_tiles(x, u, qmax=127, block_d=65536, mask=mask)

    text = _compiled_text(fn, one_chip, ((2, MLP), jnp.float32),
                          ((2, MLP), jnp.float32), ((2,), jnp.float32))
    assert "tpu_custom_call" in text


def test_masked_dequant_accumulate_compiles_for_v5e(one_chip):
    def fn(acc, q, scales, w, mask):
        return qk.dequant_accumulate_tiles(acc, q, scales, w * mask)

    tiles, n_blk = _tiles(2, MLP)
    text = _compiled_text(fn, one_chip, ((2, MLP), jnp.float32),
                          (tiles, jnp.int8), ((2, n_blk), jnp.float32),
                          ((2,), jnp.float32), ((2,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [8, 95])
def test_kv_row_quantize_compiles_for_v5e(one_chip, rows):
    """The int8 KV pool's write: one decode token per slot (8 slots), or a
    95-token prompt's rows at admission."""
    from repro.models.attention import KV_SCALE_BLOCK

    def fn(x):
        u = jnp.full(x.shape, 0.5, jnp.float32)
        return qk.quantize_tiles(x, u, qmax=127, block_d=KV_SCALE_BLOCK)

    text = _compiled_text(fn, one_chip, ((rows, KV_D), jnp.float32))
    assert "tpu_custom_call" in text
