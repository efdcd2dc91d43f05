"""Bit-exact trajectory anchors for the legacy mixer matrix.

``gen`` mode runs every shipped mixer config for ``N_ROUNDS`` consensus
rounds on a deterministic synthetic trajectory and records a sha256 of the
mixed parameters plus every ``CommState`` field (digests for pytrees, exact
values for scalars) into ``mixer_anchors.json``.  ``check`` mode re-runs the
same configs and asserts every record matches — this is the equivalence
gate of the Topology x Transport x Wire refactor: the anchors were captured
from the pre-refactor classes, so any layer decomposition that is not
bit-exact fails here, field by field.

The two groups isolate device requirements:

* ``dense``  — single-device einsum/simulation mixers (run in-process).
* ``gossip`` — shard_map/ppermute lowerings; needs 8 host devices, so the
  test harness launches it as a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set below when
  invoked directly with the gossip group).

Usage:
    PYTHONPATH=src python tests/data/gen_mixer_anchors.py gen --group dense
    PYTHONPATH=src python tests/data/gen_mixer_anchors.py gen --group gossip
    PYTHONPATH=src python tests/data/gen_mixer_anchors.py check --group dense
"""

from __future__ import annotations

import os
import sys

if "gossip" in sys.argv[1:]:
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

N_ROUNDS = 6  # > 2x the local-update period so several consensus rounds fire
_OUT = pathlib.Path(__file__).with_name("mixer_anchors.json")


def _sha(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _perturb(theta, r):
    """Deterministic between-round parameter drift (stands in for the
    optimizer step): pure jnp, traced round index, no PRNG."""
    rf = jnp.asarray(r, jnp.float32)

    def leaf(x):
        wave = 0.05 * jnp.cos(jnp.arange(x.size, dtype=jnp.float32) + rf)
        return x + wave.reshape(x.shape).astype(x.dtype)

    return jax.tree.map(leaf, theta)


def _state_record(state) -> dict:
    """One JSON-able record per CommState field: None for empty (), exact
    scalar values for accounting fields, sha256 digests for pytrees."""
    rec = {}
    for name, v in state._asdict().items():
        if isinstance(v, tuple) and v == ():
            rec[name] = None
        elif name in ("rounds", "ef_rounds"):
            rec[name] = int(np.asarray(v))
        elif name in ("res_norm", "res_ref", "wire_bits", "ef_drift"):
            rec[name] = float(np.asarray(v))
        else:
            rec[name] = _sha(v)
    return rec


def _run_trajectory(mixer, theta):
    state = mixer.init_state(theta)

    @jax.jit
    def step(th, st, r):
        th = _perturb(th, r)
        return mixer(th, st, round=r)

    for i in range(N_ROUNDS):
        theta, state = step(theta, state, jnp.int32(i))
    rec = {"theta": _sha(theta)}
    rec.update(_state_record(state))
    return rec


def _theta(shapes: dict, seed: int = 42):
    key = jax.random.PRNGKey(seed)
    out = {}
    for name, shape in sorted(shapes.items()):
        key, sub = jax.random.split(key)
        out[name] = jax.random.normal(sub, shape, jnp.float32)
    return out


# -- the config matrix --------------------------------------------------------


def dense_configs():
    """Single-device mixers: the dense/einsum simulation lowerings."""
    from repro.comm import CompressionConfig, ScheduleConfig
    from repro.comm.mixers import CompressedDenseMixer
    from repro.core.consensus import DenseMixer, IdentityMixer, RepeatMixer
    from repro.dynamics.faults import FaultConfig
    from repro.dynamics.local import LocalUpdateMixer
    from repro.dynamics.mixers import (
        DynamicCompressedDenseMixer,
        DynamicDenseMixer,
    )
    from repro.dynamics.schedule import DropoutSchedule, StaticSchedule
    from repro.graphs import build_graph, metropolis_weights

    w = metropolis_weights(build_graph("ring", 8))
    cc = CompressionConfig
    theta = _theta({"a": (8, 48), "b": (8, 3, 10)})
    configs = {
        "identity": lambda: IdentityMixer(),
        "dense_plain": lambda: DenseMixer(w),
        "repeat_dense": lambda: RepeatMixer(DenseMixer(w), 2),
        "dense_int8_mem": lambda: CompressedDenseMixer(
            w, cc(kind="int8", error_feedback=False, seed=11)),
        "dense_int8_ef": lambda: CompressedDenseMixer(
            w, cc(kind="int8", seed=11)),
        "dense_topk_ef": lambda: CompressedDenseMixer(
            w, cc(kind="topk", ratio=0.25, seed=11)),
        "dense_int8_sched": lambda: CompressedDenseMixer(
            w, cc(kind="int8", seed=11,
                  schedule=ScheduleConfig(kind="adaptive", warmup_rounds=2))),
        "dense_dyn_plain": lambda: DynamicDenseMixer(
            DropoutSchedule(w, 0.3, seed=5)),
        "dense_dyn_faults": lambda: DynamicDenseMixer(
            StaticSchedule(w),
            faults=FaultConfig(straggler_p=0.2, seed=3)),
        "dense_dyn_int8_ef": lambda: DynamicCompressedDenseMixer(
            DropoutSchedule(w, 0.3, seed=5), cc(kind="int8", seed=11)),
        "local_gt": lambda: LocalUpdateMixer(
            DenseMixer(w), 2, gradient_tracking=True),
        "local_h3_int8": lambda: LocalUpdateMixer(
            CompressedDenseMixer(w, cc(kind="int8", seed=11)), 3),
        "local_gt_dynamic": lambda: LocalUpdateMixer(
            DynamicDenseMixer(DropoutSchedule(w, 0.3, seed=5)), 2,
            gradient_tracking=True),
    }
    return configs, theta


def gossip_configs():
    """shard_map/ppermute lowerings over an 8-host-device node mesh."""
    from jax.sharding import PartitionSpec as P

    from repro.comm import CompressionConfig
    from repro.comm.mixers import CompressedGossipMixer
    from repro.core.consensus import GossipMixer, HierarchicalMixer
    from repro.dynamics.faults import FaultConfig
    from repro.dynamics.mixers import (
        DynamicCompressedGossipMixer,
        DynamicGossipMixer,
    )
    from repro.dynamics.schedule import DropoutSchedule, StaticSchedule
    from repro.graphs import (
        build_graph,
        metropolis_weights,
        permutation_decomposition,
    )
    from repro.launch.mesh import make_auto_mesh

    k = 8
    w = metropolis_weights(build_graph("ring", k))
    decomp = permutation_decomposition(w)
    mesh = make_auto_mesh((k,), ("data",))
    specs = {"a": P("data", None), "b": P("data", None, None)}
    cc = CompressionConfig
    theta = _theta({"a": (k, 64), "b": (k, 3, 5)})

    # hierarchical: 4 nodes x 2 replicas on the same 8 devices
    w4 = metropolis_weights(build_graph("ring", 4))
    decomp4 = permutation_decomposition(w4)
    mesh2 = make_auto_mesh((2, 4), ("rep", "data"))
    theta4 = _theta({"a": (4, 64), "b": (4, 3, 5)})

    configs = {
        "gossip_plain": lambda: GossipMixer(decomp, mesh, "data", specs),
        "gossip_int8_ef": lambda: CompressedGossipMixer(
            decomp, mesh, "data", specs, cc(kind="int8", seed=7)),
        "hier_plain": lambda: HierarchicalMixer(
            decomp4, mesh2, "data", "rep", specs),
        "hier_int8_ef": lambda: CompressedGossipMixer(
            decomp4, mesh2, "data", specs, cc(kind="int8", seed=7),
            replica_axis="rep"),
        "gossip_dyn_plain": lambda: DynamicGossipMixer(
            DropoutSchedule(w, 0.3, seed=5), mesh, "data", specs),
        "gossip_dyn_quant_mem": lambda: DynamicGossipMixer(
            DropoutSchedule(w, 0.3, seed=5), mesh, "data", specs,
            quantized=cc(kind="int8", error_feedback=False, seed=7)),
        "gossip_dyn_int8_ef_b2": lambda: DynamicGossipMixer(
            DropoutSchedule(w, 0.3, seed=5), mesh, "data", specs,
            quantized=cc(kind="int8", seed=7), ef_rebase_every=2),
        "gossip_dyn_int8_ef_adaptive": lambda: DynamicCompressedGossipMixer(
            DropoutSchedule(w, 0.3, seed=5), mesh, "data", specs,
            cc(kind="int8", seed=7), ef_rebase_every=8,
            ef_rebase_threshold=0.05),
        "gossip_dyn_faults": lambda: DynamicGossipMixer(
            StaticSchedule(w), mesh, "data", specs,
            faults=FaultConfig(link_drop_p=0.3, seed=3)),
    }
    per_config_theta = {"hier_plain": theta4, "hier_int8_ef": theta4}
    return configs, theta, per_config_theta


def run_group(group: str) -> dict:
    if group == "dense":
        configs, theta = dense_configs()
        per_config_theta = {}
    else:
        configs, theta, per_config_theta = gossip_configs()
    out = {}
    for name, make in configs.items():
        t = per_config_theta.get(name, theta)
        out[name] = _run_trajectory(make(), t)
        print(f"  {name}: theta={out[name]['theta'][:12]} "
              f"wire_bits={out[name]['wire_bits']}")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("mode", choices=["gen", "check"])
    ap.add_argument("--group", required=True, choices=["dense", "gossip"])
    ap.add_argument("--out", default=str(_OUT))
    args = ap.parse_args()
    path = pathlib.Path(args.out)

    print(f"[{args.mode}] group={args.group} devices={jax.device_count()}")
    records = run_group(args.group)

    if args.mode == "gen":
        merged = {}
        if path.exists():
            merged = json.loads(path.read_text())
        merged[args.group] = records
        path.write_text(json.dumps(merged, indent=1, sort_keys=True) + "\n")
        print(f"wrote {len(records)} anchors to {path}")
        return

    anchors = json.loads(path.read_text())[args.group]
    failures = []
    for name, rec in anchors.items():
        if name not in records:
            failures.append(f"{name}: config missing from current matrix")
            continue
        for field, want in rec.items():
            got = records[name].get(field)
            if got != want:
                failures.append(f"{name}.{field}: {got!r} != anchor {want!r}")
    for extra in set(records) - set(anchors):
        failures.append(f"{extra}: not in anchor file (re-gen to add)")
    if failures:
        print("ANCHOR MISMATCH:")
        for f in failures:
            print("  " + f)
        raise SystemExit(1)
    print(f"all {len(anchors)} {args.group} anchors match bit-exactly")


if __name__ == "__main__":
    main()
