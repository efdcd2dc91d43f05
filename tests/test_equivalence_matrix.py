"""Equivalence matrix of the Topology × Transport × Wire stacks.

Every factory and argument mix must (a) build the expected layer stack
behind :class:`repro.comm.composed.ComposedMixer` and (b) reproduce its
trajectory bit-exactly, field by field, against the anchors in
``tests/data/mixer_anchors.json`` (captured from the classes that preceded
the layers).
The anchor replay runs ``tests/data/gen_mixer_anchors.py check`` in a
subprocess per device group; checkpoints written under the old class layout
must restore through ``COMM_STATE_PAD`` and continue bit-exactly on the
composed stack.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "..", "src")
_ANCHORS = os.path.join(_HERE, "data", "gen_mixer_anchors.py")


def _check_group(group, devices=None):
    env = dict(os.environ)
    if devices is not None:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, _ANCHORS, "check", "--group", group],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_dense_group_matches_pre_refactor_anchors():
    out = _check_group("dense")
    assert "anchors match bit-exactly" in out


def test_gossip_group_matches_pre_refactor_anchors():
    out = _check_group("gossip", devices=8)
    assert "anchors match bit-exactly" in out


def test_every_legacy_name_is_a_composed_shim():
    """The class matrix is gone: each factory and argument mix returns one
    ComposedMixer whose (topology, transport, wire) stack and scope name
    are these (RepeatMixer/LocalUpdateMixer wrap one).  The gossip rows
    are covered by the anchor replay (they need a device mesh)."""
    from repro.comm import (
        ChocoWire,
        CodecWire,
        CompressionConfig,
        ComposedMixer,
        DenseTransport,
        IdentityWire,
        ScheduledTopology,
        StarTopology,
        StarTransport,
        StaticTopology,
    )
    from repro.core.consensus import (
        RepeatMixer,
        make_dense_mixer,
        make_hub_mixer,
        make_identity_mixer,
    )
    from repro.dynamics.faults import FaultConfig
    from repro.dynamics.local import LocalUpdateMixer
    from repro.dynamics.schedule import DropoutSchedule, StaticSchedule
    from repro.graphs import build_graph, metropolis_weights

    w = metropolis_weights(build_graph("ring", 8))
    cc = CompressionConfig(kind="int8", seed=11)
    mem = CompressionConfig(kind="int8", error_feedback=False, seed=11)
    drop = DropoutSchedule(w, 0.3, seed=5)
    faults = FaultConfig(straggler_p=0.2, seed=3)
    table = [
        (make_identity_mixer(), None, None, IdentityWire, "identity"),
        (make_dense_mixer(w), StaticTopology, DenseTransport, IdentityWire,
         "dense"),
        (make_dense_mixer(w, compression=cc), StaticTopology,
         DenseTransport, ChocoWire, "dense"),
        (make_dense_mixer(w, compression=mem), StaticTopology,
         DenseTransport, CodecWire, "dense"),
        (make_dense_mixer(None, schedule=drop), ScheduledTopology,
         DenseTransport, IdentityWire, "dense"),
        (make_dense_mixer(None, schedule=StaticSchedule(w), faults=faults),
         ScheduledTopology, DenseTransport, IdentityWire, "dense"),
        (make_dense_mixer(None, compression=cc, schedule=drop),
         ScheduledTopology, DenseTransport, ChocoWire, "dense"),
        (make_hub_mixer(8), StarTopology, StarTransport, IdentityWire,
         "hub"),
        (make_hub_mixer(8, cc), StarTopology, DenseTransport, ChocoWire,
         "hub"),
    ]
    for m, topo, transport, wire, name in table:
        assert type(m) is ComposedMixer
        assert (topo is None and m.topo is None) or type(m.topo) is topo
        assert ((transport is None and m.transport is None)
                or type(m.transport) is transport)
        assert type(m.wire) is wire, (name, type(m.wire))
        assert m.name == name
    # the EF wire carries no re-base clock off the time-varying gossip stack
    assert all(getattr(m.wire, "clock", None) is None for m, *_ in table)
    wrappers = [
        RepeatMixer(make_dense_mixer(w), 2),
        LocalUpdateMixer(make_dense_mixer(w), 2, gradient_tracking=True),
        LocalUpdateMixer(make_hub_mixer(8), 4, gradient_tracking=True),
    ]
    for m in wrappers:
        assert type(m.inner) is ComposedMixer, type(m).__name__
        assert m.name == type(m).__name__


def _toy_trainer(**kw):
    from repro.core import TrainerSpec

    def loss_fn(params, batch):
        return jnp.mean((params["w"] - batch) ** 2)

    spec = TrainerSpec(num_nodes=4, graph="ring", robust=False, lr=0.1,
                       seed=0, **kw)
    return spec.build(loss_fn)


def _batch(i, k=4):
    rng = np.random.default_rng(100 + i)
    return jnp.asarray(rng.normal(size=(k, 2)), jnp.float32)


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_pre_refactor_checkpoint_restores_onto_composed_stack(tmp_path):
    """A checkpoint written under the old class layout (positionally-stored
    CommState, truncated to the 8-field pre-PR5 schema) restores via
    COMM_STATE_PAD and continues BIT-exactly on the composed EF codec
    stack — the wires' state re-layout kept every field's position."""
    from repro.checkpoint import restore_train_state, save_checkpoint

    tr = _toy_trainer(compress="int8")
    state = tr.init({"w": jnp.zeros((4, 2))})
    state, _ = tr.step(state, _batch(0))
    state, _ = tr.step(state, _batch(1))

    old_layout = {
        "params": state.params,
        "opt_state": state.opt_state,
        "step": state.step,
        "comm": tuple(state.comm)[:8],  # pre-refactor on-disk schema
    }
    save_checkpoint(str(tmp_path), 2, old_layout)
    restored, step = restore_train_state(str(tmp_path))
    assert step == 2
    assert restored.comm.ef_rounds == () and restored.comm.ef_drift == ()
    _assert_trees_equal(state, restored)

    nxt = _batch(2)
    s1, _ = tr.step(state, nxt)
    s2, _ = tr.step(restored, nxt)
    _assert_trees_equal(s1, s2)


def test_hub_scaffold_checkpoint_roundtrip_continues_bitexact(tmp_path):
    """The federated stack's state (LocalUpdateMixer tracker over the star
    transport — SCAFFOLD's control variate in CommState.track) survives the
    save/restore round-trip and the resumed run is bit-exact."""
    from repro.checkpoint import restore_train_state, save_train_state

    tr = _toy_trainer(topology="hub", local_updates=2,
                      gradient_tracking=True)
    state = tr.init({"w": jnp.zeros((4, 2))})
    # 3 steps: crosses a consensus round, leaves a live tracker correction
    for i in range(3):
        state, _ = tr.step(state, _batch(i))
    assert state.comm.track != ()

    save_train_state(str(tmp_path), 3, state)
    restored, step = restore_train_state(str(tmp_path))
    assert step == 3
    _assert_trees_equal(state, restored)

    for i in range(3, 6):
        nxt = _batch(i)
        state, _ = tr.step(state, nxt)
        restored, _ = tr.step(restored, nxt)
    _assert_trees_equal(state, restored)
