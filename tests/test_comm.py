"""Compressed-gossip subsystem: codec round-trips, unbiasedness, EF
convergence (dense + shard_map gossip lowerings), fused Pallas kernel vs
oracle, and the end-to-end comm_bytes reduction on the paper's FMNIST path."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    CommState,
    CompressionConfig,
    ef_residual,
    make_compressor,
    per_node_keys,
)
from repro.core import (
    DecentralizedTrainer,
    RobustConfig,
    make_dense_mixer,
)
from repro.graphs import metropolis_weights, ring_graph
from repro.utils.tree import tree_node_disagreement

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# -- (a) codec round-trips + unbiased stochastic rounding ----------------------

def _x(k=4, d=1000, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (k, d), jnp.float32)


def _keys(seed, k):
    """Per-node key batch for direct Compressor.compress calls."""
    return per_node_keys(jax.random.PRNGKey(seed), jnp.arange(k))


@pytest.mark.parametrize("kind,tol", [
    ("none", 0.0),
    ("bf16", 1.0 / 64),          # bf16 has 8 mantissa bits
    ("int8", 2.0 / 127),         # stochastic rounding: < 1 ulp = scale
    ("int4", 2.0 / 7),
])
def test_roundtrip_within_tolerance(kind, tol):
    x = _x()
    c = make_compressor(CompressionConfig(kind=kind))
    xh = c.decompress(c.compress(x, _keys(1, x.shape[0])), x.shape[1])
    scale = float(jnp.max(jnp.abs(x)))
    err = float(jnp.max(jnp.abs(xh - x)))
    assert err <= tol * scale + 1e-7, (kind, err)


@pytest.mark.parametrize("kind", ["topk", "randk"])
def test_sparsifier_keeps_ratio(kind):
    x = _x(d=400)
    c = make_compressor(CompressionConfig(kind=kind, ratio=0.1))
    vals, idx = c.compress(x, _keys(2, x.shape[0]))
    assert vals.shape == (4, 40) and idx.shape == (4, 40)
    xh = c.decompress((vals, idx), 400)
    nonzero = int(jnp.sum(xh != 0))
    assert nonzero <= 4 * 40
    if kind == "topk":
        # kept entries are exactly the largest-magnitude ones per node
        kept = jnp.sort(jnp.abs(vals), axis=1)[:, 0]
        dropped = jnp.where(xh == 0, jnp.abs(x), 0.0).max(axis=1)
        assert bool(jnp.all(dropped <= kept + 1e-6))


@pytest.mark.parametrize("kind", ["int8", "int4"])
def test_stochastic_rounding_unbiased(kind):
    """E[decompress(compress(x))] == x for the stochastic quantizers."""
    x = _x(k=2, d=256, seed=3)
    c = make_compressor(CompressionConfig(kind=kind))
    n = 600
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + c.decompress(
            c.compress(x, _keys(i, x.shape[0])), x.shape[1])
    mean = acc / n
    # per-element bias ~ scale/sqrt(12 n); allow 6 sigma
    scale = float(jnp.max(jnp.abs(x))) / (127 if kind == "int8" else 7)
    assert float(jnp.max(jnp.abs(mean - x))) < 6 * scale / np.sqrt(12 * n)


def test_int4_packing_halves_wire():
    c8 = make_compressor(CompressionConfig(kind="int8"))
    c4 = make_compressor(CompressionConfig(kind="int4"))
    q8, _ = c8.compress(_x(), _keys(0, 4))
    q4, _ = c4.compress(_x(), _keys(0, 4))
    assert q4.shape[1] == q8.shape[1] // 2 and q4.dtype == jnp.int8
    assert c4.payload_bytes(1000) < c8.payload_bytes(1000) * 0.6


# -- (b) EF-compressed mixers track the uncompressed consensus rate -----------

def _run_dense_mix(theta, w, compression, steps=50):
    # uniform protocol: same loop whether or not the wire is compressed
    mixer = make_dense_mixer(w, compression=compression)
    st = mixer.init_state(theta)
    step = jax.jit(mixer)
    for _ in range(steps):
        theta, st = step(theta, st)
    return theta, st


def _ring8_theta():
    rng = np.random.default_rng(0)
    return {
        "a": jnp.asarray(rng.normal(size=(8, 64)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(8, 3, 5)), jnp.float32),
    }


@pytest.mark.parametrize("kind", ["bf16", "int8", "int4"])
def test_ef_dense_matches_uncompressed_order(kind):
    """Acceptance (b), dense lowering: disagreement after 50 rounds on a
    ring of K=8 lands within an order of magnitude of exact mixing."""
    w = metropolis_weights(ring_graph(8))
    theta = _ring8_theta()
    t_unc, _ = _run_dense_mix(theta, w, None)
    d_unc = float(tree_node_disagreement(t_unc))
    t_c, st = _run_dense_mix(theta, w, CompressionConfig(kind=kind))
    d_c = float(tree_node_disagreement(t_c))
    assert d_c <= 10 * d_unc, (kind, d_c, d_unc)
    # node average preserved exactly (doubly-stochastic correction)
    for k in theta:
        np.testing.assert_allclose(
            np.asarray(jnp.mean(t_c[k], 0)), np.asarray(jnp.mean(theta[k], 0)),
            atol=1e-5)
    # the EF residual θ - θ̂ has shrunk to the innovation scale
    res = ef_residual(t_c, st)
    assert float(jnp.max(jnp.abs(res["a"]))) < 1e-3


def test_no_error_feedback_stalls_at_noise_floor():
    """The memoryless ablation stalls orders of magnitude above EF."""
    w = metropolis_weights(ring_graph(8))
    theta = _ring8_theta()
    t_unc, _ = _run_dense_mix(theta, w, None)
    d_unc = float(tree_node_disagreement(t_unc))
    t_off, _ = _run_dense_mix(
        theta, w, CompressionConfig(kind="int8", error_feedback=False))
    d_off = float(tree_node_disagreement(t_off))
    assert d_off > 100 * d_unc  # stalls at the quantization floor
    assert d_off < 1e-3         # but does not diverge


def test_topk_ef_contracts():
    """Biased sparsifier + EF + damped gamma still contracts monotonically."""
    w = metropolis_weights(ring_graph(8))
    theta = _ring8_theta()
    d0 = float(tree_node_disagreement(theta))
    t_c, _ = _run_dense_mix(theta, w, CompressionConfig(kind="topk", ratio=0.25))
    d_c = float(tree_node_disagreement(t_c))
    assert d_c < 1e-2 * d0


def test_ef_gossip_matches_uncompressed_order():
    """Acceptance (b), gossip lowering: the shard_map mixer ppermutes the
    compressed payload and still tracks exact mixing (subprocess: 8 devices)."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import CompressionConfig, make_dense_mixer, make_gossip_mixer
from repro.graphs import ring_graph, metropolis_weights, permutation_decomposition
from repro.launch.mesh import make_auto_mesh
from repro.utils.tree import tree_node_disagreement

k = 8
w = metropolis_weights(ring_graph(k))
d = permutation_decomposition(w)
mesh = make_auto_mesh((8,), ("data",))
rng = np.random.default_rng(0)
theta = {"a": jnp.asarray(rng.normal(size=(k, 64)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(k, 3, 5)), jnp.float32)}
specs = {"a": P("data", None), "b": P("data", None, None)}
t = theta
mix = make_dense_mixer(w)
mst = mix.init_state(t)
for _ in range(50):
    t, mst = mix(t, mst)
d_unc = float(tree_node_disagreement(t))
for kind in ("int8", "int4"):
    gm = make_gossip_mixer(d, mesh, "data", specs,
                           compression=CompressionConfig(kind=kind))
    st = gm.init_state(theta)
    t = theta
    step = jax.jit(gm)
    for _ in range(50):
        t, st = step(t, st)
        # one 8-device program in flight at a time: XLA:CPU's in-process
        # collectives can deadlock with several queued on a loaded host
        jax.block_until_ready(t)
    dd = float(tree_node_disagreement(t))
    assert dd <= 10 * d_unc, (kind, dd, d_unc)

# quant_gossip_round: one fused compressed exchange == acc + w * x_peer
# within one quantization step of the sender's per-block scale.
from jax.sharding import PartitionSpec
from repro.kernels.quant_gossip.ops import quant_gossip_round

x = jnp.asarray(rng.normal(size=(k, 1, 32)), jnp.float32)
acc = jnp.asarray(rng.normal(size=(k, 1, 32)), jnp.float32)
wr = jnp.full((k, 1), 0.25, jnp.float32)
perm = d.ppermute_pairs()[0]
p = PartitionSpec("data", None)

def round_body(xl, al, wl):
    return quant_gossip_round(xl[:, 0], al[:, 0], wl[:, 0], "data", perm,
                              jax.random.PRNGKey(0), interpret=True)[:, None]

out = jax.jit(jax.shard_map(
    round_body, mesh=mesh, check_vma=False,
    in_specs=(PartitionSpec("data", None, None), PartitionSpec("data", None, None), p),
    out_specs=PartitionSpec("data", None, None)))(x, acc, wr)
src = np.full(k, -1)
for s_, dst in perm:
    src[dst] = s_
expect = np.array(acc[:, 0])
scale_tol = np.abs(np.asarray(x[:, 0])).max(axis=1) / 127.0
for i in range(k):
    if src[i] >= 0:
        expect[i] = expect[i] + 0.25 * np.asarray(x[src[i], 0])
        tol = 0.25 * scale_tol[src[i]] + 1e-6
    else:
        tol = 1e-6
    assert np.max(np.abs(np.asarray(out[i, 0]) - expect[i])) <= tol, i
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


# -- (c) fused Pallas kernel vs oracle (interpret mode on CPU) ----------------

@pytest.mark.parametrize("k,d,block_d", [(4, 256, 64), (3, 1000, 1000),
                                         (1, 128, 32), (8, 512, 512)])
def test_quantize_kernel_matches_ref(k, d, block_d):
    from repro.kernels.quant_gossip.ops import quantize_blockwise
    from repro.kernels.quant_gossip.ref import quantize_blockwise_ref

    x = jax.random.normal(jax.random.PRNGKey(k * d), (k, d), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(1), (k, d), jnp.float32)
    qk, sk = quantize_blockwise(x, u, block_d=block_d, interpret=True,
                                use_kernel=True)
    qr, sr = quantize_blockwise_ref(x, u, block_d=block_d)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    # int8 wire dtype tolerance: dequantized error < 1 scale step
    from repro.kernels.quant_gossip.ref import dequantize_blockwise_ref

    xh = dequantize_blockwise_ref(qr, sr)
    assert float(jnp.max(jnp.abs(xh - x))) <= float(jnp.max(sr)) + 1e-7


@pytest.mark.parametrize("k,d,block_d", [(4, 256, 64), (2, 1000, 1000)])
def test_dequant_accumulate_kernel_matches_ref(k, d, block_d):
    from repro.kernels.quant_gossip.ops import (
        dequant_accumulate, quantize_blockwise)
    from repro.kernels.quant_gossip.ref import dequant_accumulate_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (k, d), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(1), (k, d), jnp.float32)
    acc = jax.random.normal(jax.random.PRNGKey(2), (k, d), jnp.float32)
    w = jnp.linspace(0.1, 0.5, k)
    q, s = quantize_blockwise(x, u, block_d=block_d, interpret=True,
                              use_kernel=True)
    out_k = dequant_accumulate(acc, q, s, w, interpret=True, use_kernel=True)
    out_r = dequant_accumulate_ref(acc, q, s, w)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)


def test_kernel_compressor_plugs_into_dense_mixer():
    """CompressionConfig(use_kernel=True) runs the whole EF loop through the
    Pallas kernels (interpret mode) and still reaches consensus."""
    w = metropolis_weights(ring_graph(8))
    theta = {"a": _x(8, 128, seed=5)}
    cfg = CompressionConfig(kind="int8", use_kernel=True, interpret=True,
                            block_d=64)
    t_c, _ = _run_dense_mix(theta, w, cfg, steps=30)
    t_u, _ = _run_dense_mix(theta, w, None, steps=30)
    d_c = float(tree_node_disagreement(t_c))
    d_u = float(tree_node_disagreement(t_u))
    assert d_c <= 10 * d_u + 1e-12


# -- (d) end-to-end wire-byte reduction on the FMNIST path --------------------

def _fmnist_trainer(compression):
    from repro.data import make_fmnist_like, pathological_noniid_partition
    from repro.models import mlp_apply, mlp_init
    from repro.models.paper_nets import make_classifier_loss

    ds = make_fmnist_like(n_train=400, n_test=50)
    fed = pathological_noniid_partition(ds, 8, seed=0)
    trainer = DecentralizedTrainer(
        make_classifier_loss(mlp_apply), predict_fn=mlp_apply, num_nodes=8,
        graph="ring", robust=RobustConfig(mu=6.0), lr=0.1,
        compression=compression)
    params = mlp_init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    xb, yb = fed.sample_batch(rng, 8)
    state = trainer.init(params)
    state, metrics = trainer.step(state, (jnp.asarray(xb), jnp.asarray(yb)))
    return state, metrics


def test_int8_reduces_comm_bytes_3_5x():
    """Acceptance (d): int8 cuts estimated wire bytes/round >= 3.5x."""
    _, m_base = _fmnist_trainer(None)
    state, m_int8 = _fmnist_trainer(CompressionConfig(kind="int8"))
    b0, b1 = float(m_base["comm_bytes"]), float(m_int8["comm_bytes"])
    assert b0 > 0 and b1 > 0
    assert b0 / b1 >= 3.5, (b0, b1, b0 / b1)
    # ef_state is live: public copies exist and step advanced
    assert isinstance(state.ef_state, CommState)
    assert jax.tree.leaves(state.ef_state.hat)


def test_topk_reduces_comm_bytes_further():
    _, m_base = _fmnist_trainer(None)
    _, m_topk = _fmnist_trainer(CompressionConfig(kind="topk", ratio=0.01))
    assert float(m_base["comm_bytes"]) / float(m_topk["comm_bytes"]) >= 20


def test_compression_config_validation():
    with pytest.raises(ValueError):
        CompressionConfig(kind="float8")
    with pytest.raises(ValueError):
        CompressionConfig(kind="topk", ratio=0.0)
    with pytest.raises(ValueError):
        CompressionConfig(kind="int4", use_kernel=True)
    with pytest.raises(ValueError):
        DecentralizedTrainer(
            lambda p, b: jnp.float32(0.0), num_nodes=4, graph="ring",
            mixer=make_dense_mixer(metropolis_weights(ring_graph(4))),
            compression=CompressionConfig(kind="int8"))
