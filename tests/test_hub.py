"""Hub-and-spoke (federated) lowering: the Topology × Transport × Wire
stack cashed in.

Anchors:
  * make_hub_mixer (StarTopology × StarTransport × IdentityWire) equals the dense
    simulation of W = 11ᵀ/K and reaches exact consensus in ONE round;
  * make_hub_mixer routes compression through the dense codec stack with
    the star W (server averages the reconstructed client innovations);
  * LocalUpdateMixer(make_hub_mixer(K), H) is FedAvg; adding gradient_tracking is
    the SCAFFOLD control variate — both train through TrainerSpec via
    --topology hub;
  * DynamicsConfig rejects hub + faults (the star has no fault model yet).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.comm import (
    ChocoWire,
    CommState,
    CompressionConfig,
    DenseTransport,
    StarTransport,
)
from repro.core import TrainerSpec
from repro.core.consensus import make_dense_mixer, make_hub_mixer
from repro.dynamics import (
    DynamicsConfig,
    FaultConfig,
    LocalUpdateMixer,
    build_dynamic_mixer,
)

K = 8


def _theta(k=K, seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(k, 6, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(k, 5)), jnp.float32)}


def test_hub_is_exact_one_round_consensus():
    theta = _theta()
    mixer = make_hub_mixer(K)
    out, comm = jax.jit(mixer)(theta, mixer.init_state(theta))
    for name, x in theta.items():
        mean = np.mean(np.asarray(x, np.float32), axis=0)
        got = np.asarray(out[name])
        # every node holds the identical global average after one round
        np.testing.assert_array_equal(got, np.broadcast_to(got[0], got.shape))
        np.testing.assert_allclose(got[0], mean, rtol=1e-6, atol=1e-7)
    assert int(comm.rounds) == 1
    # K uploads + K downloads of the per-node block
    assert mixer.bytes_per_round(theta) == 2 * sum(
        x.size * 4 for x in theta.values())
    assert float(comm.wire_bits) == 8.0 * mixer.bytes_per_round(theta)


def test_hub_matches_dense_star_matrix():
    theta = _theta()
    hub = make_hub_mixer(K)
    dense = make_dense_mixer(np.full((K, K), 1.0 / K))
    th, _ = jax.jit(hub)(theta, hub.init_state(theta))
    td, _ = jax.jit(dense)(theta, dense.init_state(theta))
    for name in theta:
        np.testing.assert_allclose(np.asarray(th[name]),
                                   np.asarray(td[name]),
                                   rtol=1e-6, atol=1e-7)


def test_hub_protocol_state_is_trivial():
    theta = _theta()
    hub = make_hub_mixer(K)
    st = hub.init_state(theta)
    assert isinstance(st, CommState)
    assert st.hat == () and st.hat_mix == () and st.track == ()
    assert hub.compression is None and hub.traced_wire is False
    # audit_wire contract: the star simulation emits no collectives
    assert hub.wire_dtype_bytes(theta) is None


def test_hub_consensus_scope_name():
    theta = _theta()
    hub = make_hub_mixer(K)
    lowered = jax.jit(hub).lower(theta, hub.init_state(theta))
    hlo = lowered.compiler_ir("hlo").as_hlo_module().to_string()
    assert "obs:consensus/hub" in hlo


def test_make_hub_mixer_compressed_rides_dense_star():
    m = make_hub_mixer(K, CompressionConfig(kind="int8", seed=3))
    assert isinstance(m.transport, DenseTransport)
    assert isinstance(m.wire, ChocoWire)
    np.testing.assert_allclose(np.asarray(m.w), np.full((K, K), 1.0 / K),
                               rtol=1e-7)
    theta = _theta()
    out, comm = jax.jit(m)(theta, m.init_state(theta))
    # the quantized server average still contracts hard toward consensus
    spread0 = max(np.ptp(np.asarray(x), axis=0).max()
                  for x in theta.values())
    spread1 = max(np.ptp(np.asarray(out[name]), axis=0).max()
                  for name in theta)
    assert spread1 < 0.1 * spread0
    assert m.compression is not None and int(comm.rounds) == 1
    # uncompressed falls back to the star transport
    assert isinstance(make_hub_mixer(K).transport, StarTransport)
    assert isinstance(make_hub_mixer(K, None).transport, StarTransport)


def test_dynamics_config_hub_validation():
    assert DynamicsConfig(topology="hub").enabled
    DynamicsConfig(topology="hub",
                   faults=FaultConfig())  # disabled faults pass
    with pytest.raises(ValueError, match="hub"):
        DynamicsConfig(topology="hub",
                       faults=FaultConfig(straggler_p=0.2))


def test_build_dynamic_mixer_hub_paths():
    w = np.full((K, K), 1.0 / K)
    m = build_dynamic_mixer(DynamicsConfig(topology="hub"), w)
    assert isinstance(m.transport, StarTransport)
    fed = build_dynamic_mixer(
        DynamicsConfig(topology="hub", local_updates=4), w)
    assert isinstance(fed, LocalUpdateMixer) and fed.period == 4
    assert isinstance(fed.inner.transport, StarTransport) and not fed.gt
    scaffold = build_dynamic_mixer(
        DynamicsConfig(topology="hub", local_updates=4,
                       gradient_tracking=True), w)
    assert scaffold.gt and isinstance(scaffold.inner.transport,
                                      StarTransport)
    comp = build_dynamic_mixer(
        DynamicsConfig(topology="hub"), w,
        compression=CompressionConfig(kind="int8"))
    assert isinstance(comp.transport, DenseTransport)
    assert isinstance(comp.wire, ChocoWire)


def test_fedavg_rounds_local_then_exact_average():
    theta = _theta()
    fed = LocalUpdateMixer(make_hub_mixer(K), 3)
    st = fed.init_state(theta)
    t = theta
    step = jax.jit(fed)
    # rounds 0, 1: local (no wire, θ untouched)
    for r in range(2):
        t, st = step(t, st)
        assert float(st.wire_bits) == 0.0
        for name in theta:
            np.testing.assert_array_equal(np.asarray(t[name]),
                                          np.asarray(theta[name]))
    # round 2 = H−1: the exact server average
    t, st = step(t, st)
    assert float(st.wire_bits) > 0.0
    for name, x in theta.items():
        mean = np.mean(np.asarray(x, np.float32), axis=0)
        np.testing.assert_allclose(np.asarray(t[name]),
                                   np.broadcast_to(mean, x.shape),
                                   rtol=1e-6, atol=1e-7)
    assert int(st.rounds) == 3


def test_scaffold_trains_through_trainer_spec():
    """--topology hub --local-updates 2 --gradient-tracking: FedAvg +
    SCAFFOLD control variate end-to-end through the trainer."""

    def loss_fn(params, batch):
        return jnp.mean((params["x"] - batch) ** 2)

    k = 4
    spec = TrainerSpec(num_nodes=k, graph="ring", robust=False, lr=0.2,
                       topology="hub", local_updates=2,
                       gradient_tracking=True, metrics_disagreement=True)
    tr = spec.build(loss_fn)
    state = tr.init({"x": jnp.zeros(3)})
    # heterogeneous targets: node i pulls toward i (the FedAvg drift setup)
    batch = jnp.broadcast_to(
        jnp.arange(k, dtype=jnp.float32)[None, :, None], (8, k, 1))
    out, ms = tr.run(state, batch)
    # consensus rounds snap disagreement to ~0 (exact server average)
    assert float(ms["disagreement"][-1]) < 1e-5
    # and the average model moved toward the global mean target 1.5
    x = np.asarray(out.params["x"])
    assert np.abs(x.mean() - 1.5) < 1.0
    assert np.isfinite(np.asarray(ms["loss_mean"])).all()


def test_hub_cli_threading():
    import argparse

    ap = argparse.ArgumentParser()
    TrainerSpec.add_cli_args(ap)
    args = ap.parse_args(["--topology", "hub", "--local-updates", "2"])
    spec = TrainerSpec.from_args(args)
    cfg = spec.dynamics_config()
    assert cfg is not None and cfg.topology == "hub"
    with pytest.raises(SystemExit):
        ap.parse_args(["--topology", "blimp"])
    # hub + stragglers must fail loudly at config build
    args = ap.parse_args(["--topology", "hub", "--straggler-p", "0.2"])
    with pytest.raises(ValueError, match="hub"):
        TrainerSpec.from_args(args).dynamics_config()
