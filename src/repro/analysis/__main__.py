"""CLI: ``python -m repro.analysis [paths...]`` — repo-discipline linter.

``--audit-smoke`` additionally compiles the shipped mixer lowerings plus the
fmnist train step and runs the jaxpr/HLO auditor over them (the CI smoke).
The env var must be set before jax import, which is why this module defers
every jax-touching import until after it is configured.
"""

from __future__ import annotations

import argparse
import os
import sys


def _audit_smoke(devices: int) -> int:
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={devices}")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.audit import audit_mixer, audit_train_step
    from repro.comm import CompressionConfig
    from repro.comm.mixers import make_dense_mixer, make_gossip_mixer
    from repro.core.spec import TrainerSpec
    from repro.dynamics.schedule import StaticSchedule
    from repro.graphs import metropolis_weights, permutation_decomposition
    from repro.graphs.topology import ring_graph

    k = devices
    w = metropolis_weights(ring_graph(k))
    decomp = permutation_decomposition(w)
    theta = {"w": jnp.zeros((k, 64), jnp.float32),
             "b": jnp.zeros((k, 8), jnp.float32)}
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:k]), ("node",))
    specs = jax.tree.map(
        lambda _: jax.sharding.PartitionSpec("node"), theta)

    failures = 0
    targets = [
        ("dense", make_dense_mixer(w), None),
        ("gossip", make_gossip_mixer(decomp, mesh, "node", specs), None),
        ("gossip-int8",
         make_gossip_mixer(decomp, mesh, "node", specs,
                           compression=CompressionConfig(kind="int8")),
         None),
        ("dynamic-ef",
         make_gossip_mixer(
             None, mesh, "node", specs,
             CompressionConfig(kind="int8", error_feedback=True),
             schedule=StaticSchedule(w), ef_rebase_every=4),
         None),
    ]
    for name, mixer, state in targets:
        report = audit_mixer(mixer, theta, state)
        status = "ok" if report.ok else "FAIL"
        print(f"audit[{name}]: {status}")
        for f in report.findings:
            print(f"  {f}")
        failures += 0 if report.ok else 1

    # the fmnist-shaped train step (tiny linear model stands in for the
    # conv net: same step structure, same mixer, same obs/donation paths)
    def loss_fn(params, batch):
        x, y = batch
        logits = x @ params["w"] + params["b"]
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits) * jax.nn.one_hot(y, 8), axis=-1))

    spec = TrainerSpec(num_nodes=k, graph="ring", mu=3.0, compress="int8")
    trainer = spec.build(loss_fn)
    state = trainer.init({"w": jnp.zeros((64, 8), jnp.float32),
                          "b": jnp.zeros((8,), jnp.float32)})
    batch = (jnp.zeros((k, 16, 64), jnp.float32),
             jnp.zeros((k, 16), jnp.int32))
    report = audit_train_step(trainer, state, batch)
    status = "ok" if report.ok else "FAIL"
    print(f"audit[train-step]: {status}")
    for f in report.findings:
        print(f"  {f}")
    failures += 0 if report.ok else 1
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repo-discipline linter + jaxpr/HLO auditor smoke")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to lint (default: src/ or .)")
    ap.add_argument("--audit-smoke", action="store_true",
                    help="also compile the shipped mixer lowerings and the "
                         "train step and run the HLO auditor over them")
    ap.add_argument("--devices", type=int, default=8,
                    help="host devices for the audit smoke (XLA_FLAGS)")
    args = ap.parse_args(argv)

    from repro.analysis.lint import lint_paths

    paths = args.paths or (["src"] if os.path.isdir("src") else ["."])
    findings = lint_paths(paths)
    for f in findings:
        print(f)
    rc = 0
    if findings:
        print(f"{len(findings)} lint finding(s)")
        rc = 1
    else:
        print("repro.analysis.lint: clean")
    if args.audit_smoke:
        rc = max(rc, _audit_smoke(args.devices))
    return rc


if __name__ == "__main__":
    sys.exit(main())
