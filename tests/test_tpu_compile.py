"""Ahead-of-time compiles of the main-path programs for a TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is described
rather than attached, so these tests run on the CPU backend and catch what
interpret mode cannot: block shapes that break the (8, 128) / (32, 128)
tiling rule, and kernels that need more VMEM than the chip has.  Kernel
shapes are qwen2-0.5b's (``configs/qwen2_0_5b.py``): the embedding and an
MLP matrix as per-node gossip leaves, and the int8 KV rows of the serving
pool.  The serving engine's decode step is compiled for two layers at
h2o-danube-1.8b widths, to see which weight converts the compiler keeps.
Jamba2-3B's prefill selective scan is compiled at its published widths.

The topology is described inside a module-scoped fixture, never at import,
and the persistent compilation cache is off while these tests compile.
"""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.quant_gossip import kernel as qk
from repro.models import TransformerLM
from repro.models.attention import paged_kv_len
from repro.serve.engine import (init_carry, make_step, narrows_weights,
                                serve_weights)

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


# -- the serving engine's decode step ----------------------------------------

DANUBE_2L = dataclasses.replace(get_arch("h2o_danube_1_8b"), n_layers=2)


@pytest.fixture(scope="module")
def danube_decode(one_chip):
    """The decode step's optimized HLO on the engine's prepared weights and
    on the float32 ones, and the element counts of the dot weights (whole,
    and one layer of a stacked one)."""
    model = TransformerLM(DANUBE_2L)
    b, max_len, ps = 2, 64, 16

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(model.param_shapes())
    assert narrows_weights(params)
    with jax.default_matmul_precision("highest"):
        assert not narrows_weights(params)
    prepared = on_chip(jax.eval_shape(
        functools.partial(serve_weights, model), params))
    nb = -(-paged_kv_len(model.cfg, "swa", max_len) // ps)
    carry = on_chip(jax.eval_shape(functools.partial(
        init_carry, model, b, {"swa": 1 + b * nb}, ps, quantized=False,
        seed=0)))
    tables = on_chip({"swa": jax.ShapeDtypeStruct((b, nb), jnp.int32)})
    step = jax.jit(make_step(model, max_len=max_len, eos=-1),
                   donate_argnums=(1,))
    hlo = {name: step.lower(p, carry, tables).compile().as_text()
           for name, p in (("prepared", prepared), ("float32", params))}
    dots = [x for x in jax.tree.leaves(prepared) if x.dtype == jnp.bfloat16]
    layer = [x.shape[1:] for x in jax.tree.leaves(prepared["groups"])
             if x.dtype == jnp.bfloat16]
    return hlo, {math.prod(s) for s in [x.shape for x in dots] + layer}


def _weight_converts(text, sizes):
    """The operands of f32-to-bf16 converts that hold as many elements as
    a dot weight."""
    dtypes = dict(re.findall(r"%([\w.\-]+) = (\w+)\[", text))
    found = []
    for dims, arg in re.findall(
            r"= bf16\[([\d,]*)\]\S* convert\(%([\w.\-]+)\)", text):
        n = math.prod(int(d) for d in dims.split(",") if d)
        if dtypes.get(arg) == "f32" and n in sizes:
            found.append(arg)
    return found


def test_prepared_weights_end_the_decode_steps_weight_converts(danube_decode):
    hlo, sizes = danube_decode
    assert _weight_converts(hlo["prepared"], sizes) == []
    # the float32 program still rounds its weights on every call
    assert _weight_converts(hlo["float32"], sizes)


# -- Jamba's prefill selective scan -------------------------------------------

def test_mamba_scan_compiles_for_v5e_in_the_prefills_scopes(one_chip,
                                                            monkeypatch):
    """The kernel at Jamba2-3B widths (an 8192-token prompt, d_inner 5120,
    N 16) fits the chip's VMEM, and its custom call carries both scopes the
    ``ssm_scan_*`` readers look for in the trace."""
    from repro.kernels.mamba_scan import ops

    monkeypatch.setattr(ops, "kernel_path", lambda use_kernel, interpret: "pallas")
    b, s, di, n = 1, 8192, 5120, 16

    def fn(u, dt, bm, cm, a, d, h0):
        with jax.named_scope("obs:serve/prefill"), \
                jax.named_scope("obs:ssm/scan"):
            return ops.selective_scan(u, dt, bm, cm, a, d, h0)

    text = _compiled_text(fn, one_chip, ((b, s, di), jnp.float32),
                          ((b, s, di), jnp.float32), ((b, s, n), jnp.float32),
                          ((b, s, n), jnp.float32), ((di, n), jnp.float32),
                          ((di,), jnp.float32), ((b, di, n), jnp.float32))
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    (op_name,) = re.findall(r'op_name="([^"]*)"', calls[0])
    assert "obs:serve/prefill" in op_name and "obs:ssm/scan" in op_name
