"""Dynamic-graph subsystem (repro.dynamics): schedules, faults, local updates.

The acceptance anchors:
  * a static TopologySchedule reproduces the frozen Dense/Gossip mixers
    bit-exactly, and a dropout schedule at p = 0 matches it;
  * dropout-renormalized matrices stay doubly stochastic and
    consensus-contractive for EVERY graphs.topology builder;
  * straggler/outage rounds report comm_bytes == 0 for masked-out links;
  * the whole thing runs in ONE compiled program per configuration
    (topology changes are traced operands — asserted via jit cache stats).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TrainerSpec
from repro.core.consensus import DenseMixer
from repro.dynamics import (
    DropoutSchedule,
    DynamicCompressedDenseMixer,
    DynamicDenseMixer,
    DynamicsConfig,
    FaultConfig,
    GeometricRedrawSchedule,
    LocalUpdateMixer,
    RoundRobinSchedule,
    StaticSchedule,
    fault_keep_matrix,
)
from repro.graphs import (
    build_graph,
    is_doubly_stochastic,
    metropolis_weights,
    metropolis_weights_traced,
    spectral_norm,
)

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ALL_BUILDERS = ["ring", "grid", "torus", "erdos_renyi", "geometric",
                "complete", "star", "hypercube"]  # K=16 suits hypercube too


def _run_subprocess(script, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


def _params(k, seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(k, 5, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(k, 7)), jnp.float32)}


# -- traced weight derivations -------------------------------------------------

@pytest.mark.parametrize("kind", ALL_BUILDERS)
def test_metropolis_traced_matches_numpy(kind):
    g = build_graph(kind, 16)
    w_np = metropolis_weights(g)
    w_tr = np.asarray(metropolis_weights_traced(
        jnp.asarray(g.adjacency, jnp.float32)))
    np.testing.assert_allclose(w_tr, w_np, atol=1e-6)


@pytest.mark.parametrize("kind", ALL_BUILDERS)
def test_dropout_renormalized_stays_doubly_stochastic(kind):
    """Every builder × dropout: per-round W is DS; E[W] stays contractive."""
    g = build_graph(kind, 16)
    w = metropolis_weights(g)
    sched = DropoutSchedule(w, p=0.4, seed=3)
    samples = []
    for r in range(40):
        wr = np.asarray(sched.round_weights(jnp.int32(r)))
        assert is_doubly_stochastic(wr, atol=1e-5), (kind, r)
        samples.append(wr)
    # consensus-contractive in expectation: the sampled mean keeps the full
    # support at (1-p)-scaled weights, so its spectral norm stays < 1
    assert spectral_norm(np.mean(samples, axis=0)) < 1.0, kind


def test_fault_masked_weights_doubly_stochastic():
    w = metropolis_weights(build_graph("erdos_renyi", 12))
    cfg = FaultConfig(link_drop_p=0.3, straggler_p=0.2, outage_p=0.2,
                      outage_len=4, seed=1)
    for r in range(12):
        keep, up = fault_keep_matrix(cfg, jnp.int32(r), 12)
        from repro.graphs import renormalize_masked_weights

        wr = np.asarray(renormalize_masked_weights(
            jnp.asarray(w, jnp.float32), keep))
        assert is_doubly_stochastic(wr, atol=1e-5), r
        # a down node's row degenerates to e_i
        up = np.asarray(up)
        for i in np.nonzero(up == 0)[0]:
            assert wr[i, i] == pytest.approx(1.0, abs=1e-5)


def test_outage_windows_are_correlated():
    cfg = FaultConfig(outage_p=0.5, outage_len=5, seed=7)
    ups = [np.asarray(fault_keep_matrix(cfg, jnp.int32(r), 10)[1])
           for r in range(10)]
    # rounds 0-4 share one outage draw, rounds 5-9 the next
    for r in range(1, 5):
        np.testing.assert_array_equal(ups[r], ups[0])
        np.testing.assert_array_equal(ups[5 + r], ups[5])


def test_round_robin_cycles_matchings():
    w = metropolis_weights(build_graph("ring", 8))
    sched = RoundRobinSchedule(w)
    m = sched.num_matchings
    assert m == 2  # even ring is 2-edge-colorable
    union = np.zeros_like(w)
    for r in range(m):
        wr = np.asarray(sched.round_weights(jnp.int32(r)))
        assert is_doubly_stochastic(wr, atol=1e-5)
        union += wr - np.diag(np.diag(wr))
    # the cycle covers exactly the base graph's off-diagonal support
    np.testing.assert_allclose(union, w - np.diag(np.diag(w)), atol=1e-6)
    # period m: round r and r+m draw the same matching
    np.testing.assert_array_equal(
        np.asarray(sched.round_weights(jnp.int32(1))),
        np.asarray(sched.round_weights(jnp.int32(1 + m))))


def test_geometric_redraw_is_ds_and_varies():
    sched = GeometricRedrawSchedule(10, radius=0.6, seed=2)
    w0 = np.asarray(sched.round_weights(jnp.int32(0)))
    w1 = np.asarray(sched.round_weights(jnp.int32(1)))
    assert is_doubly_stochastic(w0, atol=1e-5)
    assert is_doubly_stochastic(w1, atol=1e-5)
    assert not np.array_equal(w0, w1)  # support actually moves
    with pytest.raises(ValueError):
        sched.decomposition()  # dense-only: no static gossip support


# -- bit-exact reproduction of the frozen mixers -------------------------------

def test_static_schedule_reproduces_dense_mixer_bitexact():
    k = 8
    w = metropolis_weights(build_graph("erdos_renyi", k))
    params = _params(k)
    ref, _ = DenseMixer(w)(params, DenseMixer(w).init_state(params))
    for sched in (StaticSchedule(w), DropoutSchedule(w, 0.0, seed=9)):
        mixer = DynamicDenseMixer(sched)
        out, comm = jax.jit(mixer)(params, mixer.init_state(params))
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(comm.rounds) == 1


def test_static_schedule_reproduces_gossip_mixer_bitexact():
    """Subprocess (8 host devices): DynamicGossipMixer(StaticSchedule) and
    DropoutSchedule(p=0) are bit-identical to today's GossipMixer; a full
    straggler round reports wire_bits == 0 and leaves θ untouched."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.consensus import GossipMixer
from repro.dynamics import (DynamicGossipMixer, StaticSchedule,
                            DropoutSchedule, FaultConfig)
from repro.graphs import metropolis_weights, ring_graph, permutation_decomposition
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("data",))
specs = {"a": P("data", None)}
rng = np.random.default_rng(0)
params = {"a": jnp.asarray(rng.normal(size=(k, 6)), jnp.float32)}

gm = GossipMixer(permutation_decomposition(w), mesh, "data", specs)
ref, _ = jax.jit(gm)(params, gm.init_state(params))
for sched in (StaticSchedule(w), DropoutSchedule(w, 0.0, seed=4)):
    dg = DynamicGossipMixer(sched, mesh, "data", specs)
    out, comm = jax.jit(dg)(params, dg.init_state(params))
    np.testing.assert_array_equal(np.asarray(ref["a"]), np.asarray(out["a"]))
    assert float(comm.wire_bits) == 8.0 * gm.bytes_per_round(params)

dgs = DynamicGossipMixer(StaticSchedule(w), mesh, "data", specs,
                         faults=FaultConfig(straggler_p=0.999, seed=1))
out, comm = jax.jit(dgs)(params, dgs.init_state(params))
assert float(comm.wire_bits) == 0.0, float(comm.wire_bits)
np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(params["a"]),
                           atol=1e-6)
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


# -- fault accounting ----------------------------------------------------------

def test_full_straggler_round_reports_zero_comm_bytes():
    """Masked-out links put nothing on the wire: a round where every node
    straggles reports comm_bytes == 0 through the train-step metrics."""

    def loss_fn(params, batch):
        return jnp.sum(params["x"] ** 2)

    k = 6
    spec = TrainerSpec(num_nodes=k, graph="ring", robust=False, lr=0.01,
                       straggler_p=0.999, metrics_disagreement=False)
    tr = spec.build(loss_fn)
    state = tr.init({"x": jnp.ones(4)})
    batches = jnp.zeros((5, k, 1))
    state, ms = tr.run(state, batches)
    np.testing.assert_array_equal(np.asarray(ms["comm_bytes"]),
                                  np.zeros(5, np.float32))
    np.testing.assert_array_equal(np.asarray(ms["wire_bits"]),
                                  np.zeros(5, np.float32))


def _mixed_up_round(straggler_p, k, seed):
    """First round whose straggler draw has both up and down nodes."""
    cfg = FaultConfig(straggler_p=straggler_p, seed=seed)
    for r in range(64):
        up = np.asarray(fault_keep_matrix(cfg, jnp.int32(r), k)[1])
        if 0 < up.sum() < k:
            return r, up
    raise AssertionError("no mixed straggler round in 64 draws")


def test_straggler_skips_compute_freezes_down_nodes():
    """With straggler_skips_compute a down node loses its gradient too:
    its robust scale is zeroed, so its params pass the round untouched
    (no local update, no send, no receive), while up nodes keep moving."""

    def loss_fn(params, batch):
        return jnp.sum(params["x"] ** 2)

    k = 8
    r0, up = _mixed_up_round(0.5, k, seed=3)
    assert r0 == 0, "pick a seed whose round-0 draw is mixed"
    spec = TrainerSpec(num_nodes=k, graph="ring", robust=True, lr=0.1,
                       straggler_p=0.5, straggler_skips_compute=True,
                       metrics_disagreement=False, seed=3)
    tr = spec.build(loss_fn)
    state = tr.init({"x": jnp.ones(4)})
    x0 = np.asarray(state.params["x"])  # snapshot: the scan donates state
    out, _ = tr.run(state, jnp.zeros((1, k, 1)))
    x1 = np.asarray(out.params["x"])
    for i in range(k):
        if up[i] == 0:
            np.testing.assert_array_equal(x1[i], x0[i])
        else:
            assert not np.array_equal(x1[i], x0[i]), i


def test_skipped_straggler_cannot_dominate_dr_weighting():
    """Worst-distribution regression: a node that produced no work must not
    receive the exponential DR weight its (stale) worst loss would earn.
    The masked scale zeroes it; without the flag the same round lets the
    down node's huge scaled gradient blow up its own parameters."""

    def loss_fn(params, batch):
        # per-node loss is driven by the batch: the down node gets a
        # worst-distribution batch with a huge target offset
        return jnp.mean((params["x"] - batch) ** 2)

    k = 8
    _, up = _mixed_up_round(0.5, k, seed=3)
    down = int(np.nonzero(up == 0)[0][0])
    batch = np.zeros((1, k, 1), np.float32)
    batch[0, down, 0] = 100.0  # the straggler holds the worst loss
    metrics = {}
    for flag in (False, True):
        spec = TrainerSpec(num_nodes=k, graph="ring", robust=True, mu=1.0,
                           lr=0.1, straggler_p=0.5,
                           straggler_skips_compute=flag,
                           metrics_disagreement=False, seed=3)
        tr = spec.build(loss_fn)
        state = tr.init({"x": jnp.zeros(4)})
        out, ms = tr.run(state, jnp.asarray(batch))
        metrics[flag] = (np.asarray(out.params["x"]), ms)
    x_off, ms_off = metrics[False]
    x_on, ms_on = metrics[True]
    # flag off: the down node's exp(loss/mu) scale drives a huge local step
    assert np.abs(x_off[down]).max() > 1.0
    # flag on: zero scale -> the down node is frozen at its init
    np.testing.assert_array_equal(x_on[down], np.zeros(4))
    # and the effective scale the step reports no longer carries the
    # straggler's exponential weight
    assert float(ms_on["scale_max"][0]) < float(ms_off["scale_max"][0])
    # up nodes are untouched by the flag (their scale is masked by 1)
    for i in np.nonzero(up == 1)[0]:
        np.testing.assert_array_equal(x_on[i], x_off[i])


def test_straggler_skips_compute_cli_threading():
    import argparse

    ap = argparse.ArgumentParser()
    TrainerSpec.add_cli_args(ap)
    args = ap.parse_args(["--straggler-p", "0.3",
                          "--straggler-skips-compute"])
    spec = TrainerSpec.from_args(args)
    assert spec.straggler_skips_compute
    faults = spec.dynamics_config().faults
    assert faults is not None and faults.straggler_skips_compute
    # default off
    args = ap.parse_args(["--straggler-p", "0.3"])
    assert not TrainerSpec.from_args(args).straggler_skips_compute


def test_dropout_comm_bytes_counts_active_links_exactly():
    k = 8
    w = metropolis_weights(build_graph("ring", k))
    sched = DropoutSchedule(w, 0.5, seed=11)
    mixer = DynamicDenseMixer(sched)
    params = _params(k)
    per_node = sum(x.size * 4 for x in jax.tree.leaves(params)) // k
    state = mixer.init_state(params)
    for r in range(4):
        wr = np.asarray(sched.round_weights(jnp.int32(r)))
        active = int((wr > 0).sum() - k)
        _, state = mixer(params, state)
        assert float(state.wire_bits) == 8.0 * per_node * active, r


# -- local updates + gradient tracking ----------------------------------------

def test_local_update_period_gates_wire():
    k = 6
    w = metropolis_weights(build_graph("ring", k))
    mixer = LocalUpdateMixer(DynamicDenseMixer(StaticSchedule(w)), 3)
    params = _params(k)
    state = mixer.init_state(params)
    theta = params
    wires = []
    for r in range(6):
        theta, state = mixer(theta, state, round=r)
        wires.append(float(state.wire_bits))
    assert wires[0] == wires[1] == 0.0
    assert wires[2] > 0.0
    assert wires[3] == wires[4] == 0.0
    assert wires[5] == wires[2]
    # local rounds pass θ through untouched
    t2, s2 = mixer(params, mixer.init_state(params), round=0)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(t2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_local_update_period_one_matches_inner_bitexact():
    k = 6
    w = metropolis_weights(build_graph("ring", k))
    params = _params(k)
    inner = DynamicDenseMixer(StaticSchedule(w))
    wrapped = LocalUpdateMixer(DynamicDenseMixer(StaticSchedule(w)), 1)
    a, _ = inner(params, inner.init_state(params))
    b, _ = wrapped(params, wrapped.init_state(params))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_gradient_tracking_reduces_local_update_drift():
    """Heterogeneous quadratic: node i pulls toward c_i.  With H=8 local
    steps, plain local SGD parks O(η·H) from the global optimum mean(c);
    the tracking correction collapses that drift by a large factor."""
    k = 8
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.normal(size=(k, 6)), jnp.float32)

    def loss_fn(params, batch):
        return jnp.sum((params["x"] - batch) ** 2)

    opt = np.asarray(c.mean(0))
    dists = {}
    for gt in (False, True):
        spec = TrainerSpec(num_nodes=k, graph="ring", robust=False, lr=0.05,
                           local_updates=8, gradient_tracking=gt,
                           metrics_disagreement=False)
        tr = spec.build(loss_fn)
        state = tr.init({"x": jnp.zeros(6)})
        state, _ = tr.run(state, jnp.broadcast_to(c[None], (400, k, 6)))
        x = np.asarray(state.params["x"])
        dists[gt] = float(np.linalg.norm(x - opt[None], axis=1).max())
    assert dists[True] < 0.5 * dists[False], dists


def test_gradient_tracking_doubles_consensus_wire():
    k = 6
    w = metropolis_weights(build_graph("ring", k))
    params = _params(k)
    plain = LocalUpdateMixer(DynamicDenseMixer(StaticSchedule(w)), 2)
    gt = LocalUpdateMixer(DynamicDenseMixer(StaticSchedule(w)), 2,
                          gradient_tracking=True)
    sp, sg = plain.init_state(params), gt.init_state(params)
    t = params
    for r in range(2):
        t, sp = plain(t, sp, round=r)
    t = params
    for r in range(2):
        t, sg = gt(t, sg, round=r)
    assert float(sg.wire_bits) == 2.0 * float(sp.wire_bits) > 0


def test_gradient_tracking_rejects_compressed_inner():
    from repro.comm import CompressionConfig
    from repro.comm.mixers import CompressedDenseMixer

    w = metropolis_weights(build_graph("ring", 6))
    inner = CompressedDenseMixer(w, CompressionConfig(kind="int8"))
    with pytest.raises(ValueError, match="uncompressed"):
        LocalUpdateMixer(inner, 2, gradient_tracking=True)


def test_mix_every_conflicts_with_local_update_period():
    def loss_fn(params, batch):
        return jnp.sum(params["x"] ** 2)

    with pytest.raises(ValueError, match="clock"):
        TrainerSpec(num_nodes=4, graph="ring", local_updates=2,
                    mix_every=2).build(loss_fn)


# -- EF compression × dynamics -------------------------------------------------

def test_compressed_dense_dynamic_matches_static_at_p0():
    """EF int8 over a dropout schedule at p = 0 is bit-identical to the
    static compressed mixer (same codec PRNG, same W)."""
    from repro.comm import CompressionConfig
    from repro.comm.mixers import CompressedDenseMixer

    k = 6
    w = metropolis_weights(build_graph("ring", k))
    cc = CompressionConfig(kind="int8", seed=3)
    params = _params(k)
    ref = CompressedDenseMixer(w, cc)
    dyn = DynamicCompressedDenseMixer(DropoutSchedule(w, 0.0, seed=1), cc)
    sa, sb = ref.init_state(params), dyn.init_state(params)
    ta, tb = params, params
    for r in range(3):
        ta, sa = ref(ta, sa)
        tb, sb = dyn(tb, sb)
    for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(sa.res_norm) == float(sb.res_norm)


def test_compressed_dynamic_converges_under_dropout():
    """EF innovation gossip keeps contracting under 30% link dropout."""
    from repro.comm import CompressionConfig

    k = 8
    w = metropolis_weights(build_graph("ring", k))
    mixer = DynamicCompressedDenseMixer(
        DropoutSchedule(w, 0.3, seed=5), CompressionConfig(kind="int8"))
    params = _params(k)
    state = mixer.init_state(params)
    theta = params

    def disagreement(t):
        return max(float(jnp.std(x, axis=0).mean())
                   for x in jax.tree.leaves(t))

    d0 = disagreement(theta)
    for r in range(30):
        theta, state = mixer(theta, state)
    assert disagreement(theta) < 0.05 * d0


# -- EF compression on the gossip lowering (hat_mix re-basing) -----------------

def test_ef_gossip_rebase_anchors():
    """The three PR-5 bit-exactness anchors (subprocess, 8 host devices):

    * an EF config on ``DynamicGossipMixer`` builds the re-based wire (the
      silent memoryless downgrade was the bug);
    * static schedule + EF ≡ the frozen ``CompressedGossipMixer`` bit-exact
      while no re-base fires (B = 0 and B > horizon), tight-allclose across
      a re-base (pure float reordering under a static W);
    * B = 1 re-bases every round: the cache is the fresh memoryless-style
      combine Σ_j W_ij(r)·θ̂_j of the public copies, and the round output
      reconstructs as θ + γ(s − θ̂);
    * dense vs gossip dynamic EF agree at a fixed seed: bit-equal θ̂ on the
      first round (the (node, leaf) PRNG fold contract), trajectory-level
      agreement after 6 dropout rounds (stochastic-rounding boundary flips
      are re-absorbed by EF);
    * B = 0 (never re-base) on a time-varying schedule is refused.
    """
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm import CompressionConfig
from repro.comm.mixers import CompressedGossipMixer
from repro.dynamics import (DynamicCompressedDenseMixer,
                            DynamicCompressedGossipMixer, DynamicGossipMixer,
                            DropoutSchedule, StaticSchedule)
from repro.graphs import metropolis_weights, ring_graph, permutation_decomposition
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("data",))
specs = {"a": P("data", None), "b": P("data", None, None)}
rng = np.random.default_rng(0)
theta = {"a": jnp.asarray(rng.normal(size=(k, 64)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(k, 3, 5)), jnp.float32)}
cc = CompressionConfig(kind="int8", seed=7)

m = DynamicGossipMixer(StaticSchedule(w), mesh, "data", specs, quantized=cc,
                       ef_rebase_every=8)
assert isinstance(m, DynamicCompressedGossipMixer), type(m)

ref = CompressedGossipMixer(permutation_decomposition(w), mesh, "data", specs, cc)
for b in (0, 8):
    dyn = DynamicCompressedGossipMixer(StaticSchedule(w), mesh, "data",
                                       specs, cc, ef_rebase_every=b)
    ta, sa = theta, ref.init_state(theta)
    tb, sb = theta, dyn.init_state(theta)
    ja, jb = jax.jit(ref), jax.jit(dyn)
    for r in range(5):
        ta, sa = ja(ta, sa)
        tb, sb = jb(tb, sb)
    for n in theta:
        np.testing.assert_array_equal(np.asarray(ta[n]), np.asarray(tb[n]))
        np.testing.assert_array_equal(np.asarray(sa.hat[n]), np.asarray(sb.hat[n]))
        np.testing.assert_array_equal(np.asarray(sa.hat_mix[n]),
                                      np.asarray(sb.hat_mix[n]))
    assert float(sa.res_norm) == float(sb.res_norm)
    assert float(sa.wire_bits) == float(sb.wire_bits)
    assert int(sb.ef_rounds) == 5

dyn = DynamicCompressedGossipMixer(StaticSchedule(w), mesh, "data", specs, cc,
                                   ef_rebase_every=2)
ta, sa = theta, ref.init_state(theta)
tb, sb = theta, dyn.init_state(theta)
ja, jb = jax.jit(ref), jax.jit(dyn)
for r in range(4):
    ta, sa = ja(ta, sa)
    tb, sb = jb(tb, sb)
for n in theta:
    np.testing.assert_allclose(np.asarray(ta[n]), np.asarray(tb[n]),
                               rtol=1e-5, atol=1e-5)

sched = DropoutSchedule(w, 0.3, seed=5)
m1 = DynamicCompressedGossipMixer(sched, mesh, "data", specs, cc,
                                  ef_rebase_every=1)
t1, s1 = jax.jit(m1)(theta, m1.init_state(theta))
w0 = np.asarray(m1._round_topology_w(jnp.int32(0)))
for n in theta:
    hat = np.asarray(s1.hat[n]).reshape(k, -1)
    s = np.asarray(s1.hat_mix[n]).reshape(k, -1)
    np.testing.assert_allclose(s, w0 @ hat, rtol=1e-5, atol=1e-6)
    out = np.asarray(theta[n]).reshape(k, -1) + m1.gamma * (s - hat)
    np.testing.assert_allclose(np.asarray(t1[n]).reshape(k, -1), out,
                               rtol=1e-5, atol=1e-6)

dm = DynamicCompressedDenseMixer(DropoutSchedule(w, 0.3, seed=5), cc)
gm = DynamicCompressedGossipMixer(DropoutSchedule(w, 0.3, seed=5), mesh,
                                  "data", specs, cc, ef_rebase_every=1)
td, sd = theta, dm.init_state(theta)
tg, sg = theta, gm.init_state(theta)
jd, jg = jax.jit(dm), jax.jit(gm)
for r in range(6):
    td, sd = jd(td, sd)
    tg, sg = jg(tg, sg)
    if r == 0:
        for n in theta:
            np.testing.assert_array_equal(np.asarray(sd.hat[n]),
                                          np.asarray(sg.hat[n]))
            np.testing.assert_allclose(np.asarray(td[n]), np.asarray(tg[n]),
                                       rtol=1e-6, atol=1e-6)
for n in theta:
    np.testing.assert_allclose(np.asarray(td[n]), np.asarray(tg[n]),
                               rtol=1e-2, atol=2e-2)

try:
    DynamicCompressedGossipMixer(DropoutSchedule(w, 0.3), mesh, "data",
                                 specs, cc, ef_rebase_every=0)
    raise AssertionError("B=0 on a dropout schedule must raise")
except ValueError:
    pass
print("OK")
"""
    _run_subprocess(script)


def test_ef_gossip_beats_memoryless_under_dropout():
    """Stall regression (subprocess): on the heterogeneous quadratic problem
    under dropout p = 0.2, the memoryless int8 wire stalls at the
    quantization noise floor while EF with periodic re-basing keeps
    contracting — EF must reach strictly lower consensus error.  Also pins
    the wire accounting: delta rounds bill int8 payloads on active links,
    re-base rounds bill f32, and the ``ef_rounds`` clock matches the round
    count."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm import CompressionConfig
from repro.core import TrainerSpec
from repro.dynamics import DynamicGossipMixer, DropoutSchedule
from repro.graphs import metropolis_weights, ring_graph
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("node",))
rng = np.random.default_rng(0)
c = jnp.asarray(rng.normal(size=(k, 6)), jnp.float32)

def loss_fn(params, batch):
    return jnp.sum((params["x"] - batch) ** 2)

def run(cfg, b):
    specs = {"x": P("node")}
    mixer = DynamicGossipMixer(DropoutSchedule(w, 0.2, seed=3), mesh, "node",
                               specs, quantized=cfg, ef_rebase_every=b)
    spec = TrainerSpec(num_nodes=k, graph="ring", robust=False, lr=0.03,
                       compress=cfg, metrics_disagreement=False)
    tr = spec.build(loss_fn, mixer=mixer)
    state = tr.init({"x": jnp.zeros(6)})
    state, ms = tr.run(state, jnp.broadcast_to(c[None], (300, k, 6)))
    x = np.asarray(state.params["x"])
    err = float(np.linalg.norm(x - x.mean(0, keepdims=True), axis=1).max())
    return err, state, ms

mem_err, _, _ = run(CompressionConfig(kind="int8", error_feedback=False), 8)
ef_err, st, ms = run(CompressionConfig(kind="int8"), 4)
assert ef_err < mem_err, (ef_err, mem_err)
assert int(st.comm.ef_rounds) == 300

# wire accounting: every 4th round bills f32 public copies, others int8
wire = np.asarray(ms["wire_bits"])
per_node_f32 = 32.0 * 6
assert wire.max() <= 16 * per_node_f32 + 1e-3  # <= all links live, f32
rebases = wire[3::4]
deltas = np.concatenate([wire[0::4], wire[1::4], wire[2::4]])
# int8 payload (6 bytes + 4-byte scale) < f32 (24 bytes) per node payload
assert np.median(rebases) > np.median(deltas)
print("consensus err: memoryless", mem_err, "ef", ef_err)
print("OK")
"""
    _run_subprocess(script)


def test_dynamic_gossip_wire_matches_hlo_collective_permute():
    """ISSUE satellite: the static ``bytes_per_round`` of the dynamic gossip
    mixers counts every union-support link (the buffers ppermute physically
    moves), while the traced ``wire_bits`` counts active links only — the
    authoritative figure.  Cross-check the static estimate against the
    compiled-HLO collective-permute bytes for the plain, memoryless-int8,
    EF-delta (B=0) and EF-re-base (B=1) programs, and a B=4 program whose
    HLO carries BOTH round modes.  Each lowering also passes the
    ``repro.analysis`` declared-vs-compiled wire audit clean."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.analysis import audit_wire, wire_summary
from repro.comm import CompressionConfig
from repro.dynamics import (DynamicCompressedGossipMixer, DynamicGossipMixer,
                            DropoutSchedule, StaticSchedule)
from repro.graphs import metropolis_weights, ring_graph
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("data",))
specs = {"a": P("data", None), "b": P("data", None, None)}
theta = {"a": jnp.zeros((k, 64), jnp.float32),
         "b": jnp.zeros((k, 3, 5), jnp.float32)}

def wire(mixer):
    findings = audit_wire(mixer, theta)
    assert findings == [], findings
    s = wire_summary(mixer, theta)
    assert s["ops"], "no collective-permute in compiled program"
    return s

cc = CompressionConfig(kind="int8", seed=0)
plain = DynamicGossipMixer(DropoutSchedule(w, 0.2, seed=1), mesh, "data", specs)
assert wire(plain)["total"] == plain.bytes_per_round(theta)

mem = DynamicGossipMixer(DropoutSchedule(w, 0.2, seed=1), mesh, "data", specs,
    quantized=CompressionConfig(kind="int8", error_feedback=False))
s_mem = wire(mem)
assert s_mem["total"] == mem.bytes_per_round(theta)
assert s_mem["by_dtype"].get("s8", 0) > 0, "int8 payload not on the wire"

# int4 rate rides the int8 container: the wire moves the same s8 buffers
# (HLO bytes unchanged) while the effective-bit accounting halves the
# entry bits — the scheduled-rate convention of repro.comm
mem4 = DynamicGossipMixer(DropoutSchedule(w, 0.2, seed=1), mesh, "data",
    specs, quantized=CompressionConfig(kind="int4", error_feedback=False))
assert wire(mem4)["total"] == s_mem["total"]
assert mem4.bytes_per_round(theta) < mem.bytes_per_round(theta)

delta = DynamicCompressedGossipMixer(StaticSchedule(w), mesh, "data", specs,
                                     cc, ef_rebase_every=0)
d_bytes = wire(delta)["total"]
assert d_bytes == delta.bytes_per_round(theta), (
    d_bytes, delta.bytes_per_round(theta))

rebase = DynamicCompressedGossipMixer(DropoutSchedule(w, 0.2, seed=1), mesh,
                                      "data", specs, cc, ef_rebase_every=1)
r_bytes = wire(rebase)["total"]
assert r_bytes == rebase.bytes_per_round(theta), (
    r_bytes, rebase.bytes_per_round(theta))

# B >= 2: ONE program holds both round modes -> HLO carries both wires
both = DynamicCompressedGossipMixer(DropoutSchedule(w, 0.2, seed=1), mesh,
                                    "data", specs, cc, ef_rebase_every=4)
assert wire(both)["total"] == d_bytes + r_bytes
# amortized static estimate sits between the two modes
assert d_bytes < both.bytes_per_round(theta) < r_bytes

# the traced accounting is bounded by the full-activity estimate and hits
# it exactly when every link is live (p = 0 schedule round)
st = delta.init_state(theta)
_, st = jax.jit(delta)(theta, st)
assert float(st.wire_bits) == 8.0 * d_bytes
print("OK")
"""
    _run_subprocess(script)


def test_masked_innovation_compress_matches_ref():
    """ISSUE satellite: the kernel compressor's sender-masked innovation
    encode (``compress_masked``) and masked receive combine
    (``accumulate_masked``) are served by the existing masked Pallas
    kernels, bit-exact against the jnp oracles given the same per-node
    keys — and an all-ones mask is bit-identical to the unmasked encode."""
    from repro.comm.compressors import (
        KernelInt8Quantizer, _uniform_rows, per_node_keys)
    from repro.kernels.quant_gossip.kernel import from_tiles
    from repro.kernels.quant_gossip.ref import (
        masked_dequant_accumulate_ref, masked_quantize_blockwise_ref)

    k, d = 6, 256
    rng = np.random.default_rng(3)
    delta = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)  # θ − θ̂
    keys = per_node_keys(jax.random.PRNGKey(11), jnp.arange(k))
    mask = jnp.asarray(np.arange(k) % 2, jnp.float32)
    comp = KernelInt8Quantizer(interpret=True)

    q, s = comp.compress_masked(delta, keys, mask)
    u = _uniform_rows(keys, d)
    qr, sr = masked_quantize_blockwise_ref(delta, u, mask)
    # the payload keeps the kernel's tile view; (K, D) is its row order
    np.testing.assert_array_equal(np.asarray(from_tiles(q, k, d)),
                                  np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # masked senders emit nothing, so their θ̂ increment dequantizes to 0
    m = np.asarray(mask)
    dq = np.asarray(comp.decompress((q, s), d))
    assert np.all(dq[m == 0] == 0)
    # all-ones mask == the unmasked encode, bitwise
    q1, s1 = comp.compress_masked(delta, keys, jnp.ones(k))
    q0, s0 = comp.compress(delta, keys)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q0))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))

    acc = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    wgt = jnp.linspace(0.1, 0.4, k)
    out = comp.accumulate_masked(acc, (q, s), wgt[:, None], mask)
    ref = masked_dequant_accumulate_ref(acc, qr, sr, wgt, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out)[m == 0],
                                  np.asarray(acc)[m == 0])


def test_ef_gossip_kernel_wire_matches_jnp_path():
    """The EF wire served by the fused masked Pallas kernels (interpret
    mode on CPU) tracks the jnp codec path: identical PRNG and one scale
    block mean the trajectories agree to float-reassociation noise, with
    any stochastic-rounding boundary flip (a one-q-step event) re-absorbed
    by the error feedback."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm import CompressionConfig
from repro.dynamics import DynamicCompressedGossipMixer, DropoutSchedule
from repro.graphs import metropolis_weights, ring_graph
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("data",))
specs = {"a": P("data", None)}
rng = np.random.default_rng(1)
theta = {"a": jnp.asarray(rng.normal(size=(k, 64)), jnp.float32)}
sched = lambda: DropoutSchedule(w, 0.3, seed=9)
jn = DynamicCompressedGossipMixer(
    sched(), mesh, "data", specs,
    CompressionConfig(kind="int8", seed=2), ef_rebase_every=3)
kr = DynamicCompressedGossipMixer(
    sched(), mesh, "data", specs,
    CompressionConfig(kind="int8", seed=2, use_kernel=True, interpret=True),
    ef_rebase_every=3)
ta, sa = theta, jn.init_state(theta)
tb, sb = theta, kr.init_state(theta)
ja, jb = jax.jit(jn), jax.jit(kr)
for r in range(5):
    ta, sa = ja(ta, sa)
    tb, sb = jb(tb, sb)
    if r == 0:
        np.testing.assert_allclose(np.asarray(sa.hat["a"]),
                                   np.asarray(sb.hat["a"]),
                                   rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(np.asarray(ta["a"]), np.asarray(tb["a"]),
                           rtol=1e-2, atol=5e-2)
assert float(sa.wire_bits) == float(sb.wire_bits)
print("OK")
"""
    _run_subprocess(script)


def test_ef_rebase_clock_composes_with_local_updates():
    """The re-base cadence follows ``CommState.ef_rounds`` (executed EF
    consensus rounds), not the step clock that ``LocalUpdateMixer``
    overwrites: with H = 2 and B = 2, steps 0/2/4/6 are local (0 wire),
    steps 1/5 are int8 delta rounds and steps 3/7 f32 re-bases."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm import CompressionConfig
from repro.dynamics import (DynamicCompressedGossipMixer, DropoutSchedule,
                            LocalUpdateMixer)
from repro.graphs import metropolis_weights, ring_graph
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("data",))
specs = {"a": P("data", None)}
rng = np.random.default_rng(0)
theta = {"a": jnp.asarray(rng.normal(size=(k, 64)), jnp.float32)}
inner = DynamicCompressedGossipMixer(
    DropoutSchedule(w, 0.0, seed=2), mesh, "data", specs,
    CompressionConfig(kind="int8", seed=1), ef_rebase_every=2)
mixer = LocalUpdateMixer(inner, 2)
state = mixer.init_state(theta)
step = jax.jit(mixer)
wires, efs = [], []
t = theta
for r in range(8):
    t, state = step(t, state, round=r)
    wires.append(float(state.wire_bits))
    efs.append(int(state.ef_rounds))
assert efs == [0, 1, 1, 2, 2, 3, 3, 4], efs
assert wires[0] == wires[2] == wires[4] == wires[6] == 0.0, wires
d = 64
per_delta = 16 * 8.0 * (d + 4)          # active links x int8 payload bits
per_rebase = 16 * 32.0 * d              # active links x f32 bits
assert wires[1] == wires[5] == per_delta, wires
assert wires[3] == wires[7] == per_rebase, wires
assert int(state.rounds) == 8  # the wrapper owns the step clock
print("OK")
"""
    _run_subprocess(script)


# -- one compiled program per configuration ------------------------------------

def test_zero_recompiles_across_dynamic_rounds():
    def loss_fn(params, batch):
        return jnp.sum((params["x"] - batch) ** 2)

    k = 6
    rng = np.random.default_rng(0)
    for kw in ({"topology": "dropout", "drop_p": 0.4},
               {"topology": "geometric"},
               {"topology": "round_robin"},
               {"topology": "dropout", "drop_p": 0.2, "local_updates": 3,
                "gradient_tracking": True},
               {"straggler_p": 0.3, "outage_p": 0.2}):
        spec = TrainerSpec(num_nodes=k, graph="ring", robust=False, lr=0.05,
                           metrics_disagreement=False, **kw)
        tr = spec.build(loss_fn)
        state = tr.init({"x": jnp.zeros(4)})
        batch = jnp.asarray(rng.normal(size=(k, 4)), jnp.float32)
        for _ in range(4):
            state, _ = tr.step(state, batch)
        assert tr._train_step._cache_size() == 1, kw


# -- masked quant_gossip kernels -----------------------------------------------

@pytest.mark.parametrize("k,d,block_d", [(4, 256, 64), (3, 1000, 1000)])
def test_masked_quantize_kernel_matches_ref(k, d, block_d):
    from repro.kernels.quant_gossip.ops import quantize_blockwise
    from repro.kernels.quant_gossip.ref import masked_quantize_blockwise_ref

    x = jax.random.normal(jax.random.PRNGKey(k * d), (k, d), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(1), (k, d), jnp.float32)
    mask = jnp.asarray(np.arange(k) % 2, jnp.float32)
    qk, sk = quantize_blockwise(x, u, mask=mask, block_d=block_d,
                                interpret=True, use_kernel=True)
    qr, sr = masked_quantize_blockwise_ref(x, u, mask, block_d=block_d)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    # masked senders put NOTHING on the wire
    m = np.asarray(mask)
    assert np.all(np.asarray(qk)[m == 0] == 0)
    assert np.all(np.asarray(sk)[m == 0] == 0)


@pytest.mark.parametrize("k,d,block_d", [(4, 256, 64), (2, 1000, 1000)])
def test_masked_dequant_accumulate_matches_ref_and_passthrough(k, d, block_d):
    from repro.kernels.quant_gossip.ops import (
        dequant_accumulate, quantize_blockwise)
    from repro.kernels.quant_gossip.ref import masked_dequant_accumulate_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (k, d), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(1), (k, d), jnp.float32)
    acc = jax.random.normal(jax.random.PRNGKey(2), (k, d), jnp.float32)
    w = jnp.linspace(0.1, 0.5, k)
    mask = jnp.asarray(np.arange(k) % 2, jnp.float32)
    q, s = quantize_blockwise(x, u, block_d=block_d, interpret=True,
                              use_kernel=True)
    out_k = dequant_accumulate(acc, q, s, w, mask, interpret=True,
                               use_kernel=True)
    out_r = masked_dequant_accumulate_ref(acc, q, s, w, mask)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)
    # a masked link contributes EXACTLY acc (bitwise), not approximately
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(out_k)[m == 0],
                                  np.asarray(acc)[m == 0])


# -- config validation ---------------------------------------------------------

def test_dynamics_config_validation():
    with pytest.raises(ValueError, match="topology"):
        DynamicsConfig(topology="wormhole")
    with pytest.raises(ValueError, match="local_updates"):
        DynamicsConfig(local_updates=0)
    with pytest.raises(ValueError, match="drop_p"):
        DynamicsConfig(topology="dropout", drop_p=1.0)
    with pytest.raises(ValueError, match="link_drop_p"):
        FaultConfig(link_drop_p=-0.1)
    with pytest.raises(ValueError, match="ef_rebase_every"):
        DynamicsConfig(ef_rebase_every=-1)
    assert not DynamicsConfig().enabled
    assert DynamicsConfig(local_updates=2).enabled
    assert DynamicsConfig(faults=FaultConfig(straggler_p=0.1)).enabled
