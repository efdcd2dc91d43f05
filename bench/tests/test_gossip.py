"""The four-chip gossip job with the int8 error-feedback wire, at smoke size
on four host devices of the CPU.

One subprocess (the main process keeps one device) builds the job as its
cell does, one node per device, and drives whole runs with the cell's own
limits: the clean program must come out correct, and each fault planted in
the program underneath must come out not correct, failing the number named
beside it:

* a neighbour dropped (one matching's weights zeroed)     -> choco_rms_ratio
* the exchange left out (every link's weight zeroed)      -> choco_rms_ratio
* a coarser wire: int4 levels, or every block scale doubled -> wire_rms_ratio
* error feedback off: the public copies reset every round -> wire_rms_ratio
* half of each node's batch left out                      -> change_rel_gap
* a step that returns its state unchanged                 -> change_rel_gap

A second test puts the wire's plain simulation, with the same faults, in
the program's place (what ``bench/calibrate.py`` reads on the chip), and
the bfloat16 reference as the control.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "train.qwen2-0.5b.k4-ring-int8ef"

#: fault -> the number it must fail
PROGRAM_FAULTS = {
    "dropped_neighbour": "choco_rms_ratio",
    "no_exchange": "choco_rms_ratio",
    "int4_wire": "wire_rms_ratio",
    "scale_doubled": "wire_rms_ratio",
    "no_error_feedback": "wire_rms_ratio",
    "half_batch": "change_rel_gap",
    "state_unchanged": "change_rel_gap",
}


def _run(mode: str, timeout: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, os.path.abspath(__file__), mode],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_program_faults_fail_and_the_clean_program_passes():
    got = _run("program", timeout=900)
    clean = got.pop("clean")
    assert clean["correct"], clean
    assert clean["checks"]["wire_rms_ratio"] == pytest.approx(0.408, abs=0.05)
    for fault, number in PROGRAM_FAULTS.items():
        r = got[fault]
        assert not r["correct"], (fault, r)
        assert r["checks"][number] > r["limits"][number], (fault, r)


def test_reference_side_faults_and_the_control_fail():
    got = _run("reference", timeout=900)
    lim = got.pop("limits")
    clean = got.pop("clean")
    assert all(clean[n] <= lim[n] for n in lim), clean
    for fault, number in PROGRAM_FAULTS.items():
        if fault in got:
            assert got[fault][number] > lim[number], (fault, got[fault])
    assert any(got["control"][n] > lim[n] for n in lim), got["control"]


# -- the subprocess -------------------------------------------------------------------

def _setup():
    from bench.tests import smoke
    from bench.harness import common

    job = common.load_json(os.path.join(common.BENCH, "traffic",
                                        "train-k4-ring-int8ef.json"))
    spec = smoke.spec(smoke.QWEN, dict(job, seq_len=64), smoke.limits(CELL))
    spec.chips = 4
    return spec


def _program_hooks(fault: str) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import make_gossip_mixer

    def gossip(change_decomp=None, change_cc=None, after=None):
        def make(decomp, mesh, specs, cc):
            if change_decomp:
                decomp = change_decomp(decomp)
            if change_cc:
                cc = change_cc(cc)
            m = make_gossip_mixer(decomp, mesh, "node", specs, cc)
            return after(m) if after else m
        return {"gossip": make}

    def no_memory(m):
        class NoMemory(type(m)):
            def __call__(self, theta, state, *, round=None):
                zero = lambda t: jax.tree.map(jnp.zeros_like, t)  # noqa: E731
                return super().__call__(
                    theta, state._replace(hat=zero(state.hat),
                                          hat_mix=zero(state.hat_mix)),
                    round=round)
        m.__class__ = NoMemory
        return m

    def halve_levels(m):
        m.compressor.qmax = 63.5        # every block scale doubled
        return m

    def freeze(trainer):
        run = trainer._run

        def unchanged(state, batches):
            _, ms = run(jax.tree.map(jnp.copy, state), batches)
            return state, ms

        unchanged._cache_size = run._cache_size
        trainer._run = unchanged

    if fault == "dropped_neighbour":
        return gossip(lambda d: dataclasses.replace(
            d, matching_weights=[0 * d.matching_weights[0]]
            + list(d.matching_weights[1:])))
    if fault == "no_exchange":
        return gossip(lambda d: dataclasses.replace(
            d, self_weights=np.ones_like(d.self_weights),
            matching_weights=[0 * w for w in d.matching_weights]))
    if fault == "int4_wire":
        return gossip(change_cc=lambda cc: dataclasses.replace(
            cc, kind="int4", use_kernel=False))
    if fault == "scale_doubled":
        return gossip(after=halve_levels)
    if fault == "no_error_feedback":
        return gossip(after=no_memory)
    if fault == "half_batch":
        return {"loss": lambda model: lambda p, b: model.loss(
            p, {"tokens": b["tokens"][:, :33]})}
    if fault == "state_unchanged":
        return {"trainer": freeze}
    raise ValueError(fault)


def _main_program():
    import jax

    from bench.harness import train

    spec = _setup()
    out = {}
    for fault in ["clean"] + list(PROGRAM_FAULTS):
        hooks = {} if fault == "clean" else _program_hooks(fault)
        result, checks = train.run(spec, jax.devices()[:4], 0.0, hooks=hooks)
        out[fault] = {"correct": result["correct"],
                      "checks": {n: c["value"] for n, c in checks.items()},
                      "limits": {n: c["limit"] for n, c in checks.items()}}
        print(fault, out[fault], file=sys.stderr, flush=True)
    print(json.dumps(out))


def _main_reference():
    import jax
    import jax.numpy as jnp

    from bench.calibrate import choco_readings

    spec = _setup()
    out = {"limits": spec.limits}
    for what, gaps in choco_readings(spec, jax.devices()[:4], dtype_low=jnp.bfloat16):
        out[what] = {n: gaps[n] for n in spec.limits}
        print(what, out[what], file=sys.stderr, flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(HERE))]
    {"program": _main_program, "reference": _main_reference}[sys.argv[1]]()
