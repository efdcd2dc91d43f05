"""repro.analysis: the jaxpr/HLO auditor, the RPR linter, and the sanitizer.

Acceptance anchors (ISSUE PR 7):
  * the auditor flags each seeded-bad fixture — a stray host callback in a
    step, an f32 payload smuggled past an int8 wire declaration, a scan
    driver whose donated carry cannot alias — and passes clean on the
    shipped lowerings;
  * ``python -m repro.analysis src/`` exits 0 (the repo lints clean);
  * ``--sanitize`` leaves the trajectory bit-exact and throws on a seeded
    protocol violation;
  * the adaptive EF re-base never fires on a static schedule and does fire
    under dropout, with ``CommState.ef_drift`` carrying the proxy.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    audit_baked_consts,
    audit_donation,
    audit_host_callbacks,
    audit_recompile,
    audit_train_step,
    lint_paths,
    lint_source,
)
from repro.comm.protocol import CommState

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_REPO = os.path.join(os.path.dirname(__file__), "..")


def _run_subprocess(script, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"


# -- linter: traced-region rules ----------------------------------------------

def test_lint_rpr001_flags_python_branch_on_traced_value():
    src = """
def train_step(state, batch):
    loss = state + batch
    if loss > 0:
        loss = loss * 2
    return loss
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR001"]
    assert findings[0].line == 4


def test_lint_rpr001_static_branches_pass():
    src = """
def train_step(state, batch, cfg=None):
    if cfg is None:
        batch = batch * 2
    if isinstance(state, dict):
        state = state["x"]
    if batch.ndim > 1:
        batch = batch.sum()
    return state + batch
"""
    assert lint_source(src, "fix.py") == []


def test_lint_rpr002_flags_host_materialization():
    src = """
def train_step(state, batch):
    x = state * batch
    scale = float(x)
    n = x.item()
    arr = np.asarray(x)
    return scale + n + arr
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR002"] * 3
    assert [f.line for f in findings] == [4, 5, 6]


def test_lint_rpr002_untraced_and_noqa_pass():
    src = """
def train_step(state, batch):
    d = float(state.shape[0])          # static shape math: fine
    b = float(mixer_bytes)  # repro: noqa[RPR002]
    return state * d * b
"""
    assert lint_source(src, "fix.py") == []


def test_lint_traced_region_propagates_to_helpers():
    src = """
def _helper(x):
    return float(x)

def train_step(state, batch):
    return _helper(state)
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR002"]


def test_lint_rpr003_partial_state_specs():
    src = """
class BadMixer(Mixer):
    def init_state(self, params):
        return CommState(hat=params, hat_mix=params, rounds=0)

    def state_specs(self, specs):
        return trivial_state_specs()._replace(hat=specs)
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR003"]
    assert "hat_mix" in findings[0].message


def test_lint_rpr003_complete_or_absent_specs_pass():
    complete = """
class GoodMixer(Mixer):
    def init_state(self, params):
        return CommState(hat=params, hat_mix=params)

    def state_specs(self, specs):
        return trivial_state_specs()._replace(hat=specs, hat_mix=specs)
"""
    assert lint_source(complete, "fix.py") == []
    # no state_specs anywhere in the module: may be inherited out-of-module
    absent = """
class InheritingMixer(Mixer):
    def init_state(self, params):
        return CommState(hat=params)
"""
    assert lint_source(absent, "fix.py") == []


def test_lint_rpr004_import_time_device_alloc():
    src = """
import jax.numpy as jnp
ZEROS = jnp.zeros((4, 4))

def make():
    return jnp.ones(3)   # inside a function: fine
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR004"]
    assert findings[0].line == 3


def test_lint_rpr005_ctor_outside_hooks():
    src = """
def sneaky(state):
    return CommState(hat=state.hat)

def init_state(self, params):
    return CommState(hat=params)
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR005"]
    assert findings[0].line == 3


def test_lint_rpr006_host_callback_outside_obs():
    src = """
from jax.experimental import io_callback
import jax

def step(x):
    io_callback(print, None, x)
    return jax.pure_callback(abs, x, x)
"""
    findings = lint_source(src, "src/repro/core/fix.py")
    assert [f.code for f in findings] == ["RPR006", "RPR006"]
    assert "MetricsSink" in findings[0].message


def test_lint_rpr006_obs_modules_and_noqa_pass():
    src = """
from jax.experimental import io_callback

def tap(x):
    io_callback(print, None, x)
"""
    # the sink itself is the one sanctioned callback site
    assert lint_source(src, "src/repro/obs/sink.py") == []
    suppressed = src.replace(
        "io_callback(print, None, x)",
        "io_callback(print, None, x)  # repro: noqa[RPR006]")
    assert lint_source(suppressed, "src/repro/core/fix.py") == []


def test_lint_layer_methods_are_traced_regions():
    """The Topology × Transport × Wire layer methods seed tracing: a host
    cast of a traced operand inside Wire.rate / Transport.apply_w /
    Topology.round_w is RPR002 even though the class is not a Mixer."""
    src = """
class FancyWire:
    def rate(self, state):
        return float(state.res_norm)

class FancyTransport:
    def apply_w(self, w, theta):
        return int(w)

class FancyTopology:
    def round_w(self, rounds):
        return float(rounds)
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR002"] * 3


def test_lint_rpr007_wire_without_spec_fields():
    src = """
class LeakyWire:
    def init_fields(self, params, incremental=False):
        fields = {"hat": params, "key": 0}
        if incremental:
            fields["hat_mix"] = params
        return fields
"""
    findings = lint_source(src, "fix.py")
    assert [f.code for f in findings] == ["RPR007"]
    assert "hat" in findings[0].message and "hat_mix" in findings[0].message


def test_lint_rpr007_declared_or_trivial_fields_pass():
    complete = """
class GoodWire:
    def init_fields(self, params, incremental=False):
        return {"hat": params, "key": 0}

    def spec_fields(self, param_specs, incremental=False):
        return {"hat": param_specs}
"""
    assert lint_source(complete, "fix.py") == []
    # inherited in-module spec_fields counts
    inherited = complete + """

class SubWire(GoodWire):
    def init_fields(self, params, incremental=False):
        return {"hat": params}
"""
    assert lint_source(inherited, "fix.py") == []
    # trivial fields (key/rounds/...) need no declaration
    trivial = """
class KeyOnlyWire:
    def init_fields(self, params, incremental=False):
        return {"key": 0}
"""
    assert lint_source(trivial, "fix.py") == []


def test_repo_lints_clean():
    """The shipped tree passes its own linter (justified noqa only)."""
    findings = lint_paths([os.path.join(_REPO, "src")])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_schema_catches_missing_pad_entry(tmp_path):
    from repro.analysis.lint import lint_schema
    proto = tmp_path / "protocol.py"
    proto.write_text(
        "class CommState(NamedTuple):\n"
        "    hat: tuple = ()\n"
        "    brand_new_field: tuple = ()\n")
    io_mod = tmp_path / "io.py"
    io_mod.write_text("COMM_STATE_PAD = {'hat': ()}\n")
    findings = lint_schema(str(proto), str(io_mod))
    assert [f.code for f in findings] == ["RPR005"]
    assert "brand_new_field" in findings[0].message


# -- auditor: seeded-bad fixtures ----------------------------------------------

def test_audit_flags_stray_host_callback():
    def probe(x):
        return x * 2.0

    def bad_step(x):
        y = x + 1.0
        y = jax.pure_callback(probe, jax.ShapeDtypeStruct(y.shape, y.dtype),
                              y)
        return y * 2.0

    findings = audit_host_callbacks(bad_step, jnp.ones(4))
    assert [f.code for f in findings] == ["host-sync"]
    assert all(f.severity == "error" for f in findings)

    def good_step(x):
        return (x + 1.0) * 2.0

    assert audit_host_callbacks(good_step, jnp.ones(4)) == []


def test_audit_allows_registered_obs_tap():
    """Callbacks from an allowed module prefix pass the audit."""
    def probe(x):
        return x

    def step(x):
        return jax.pure_callback(probe, jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype), x)

    # this test module is not under repro.obs -> flagged ...
    assert audit_host_callbacks(step, jnp.ones(2))
    # ... but allowed when its module is whitelisted
    allowed = audit_host_callbacks(step, jnp.ones(2),
                                   allowed=(__name__.split(".")[0],))
    assert allowed == []


def test_audit_flags_broken_donation():
    # output shape matches no donated input -> nothing can alias
    def reduces(state):
        return jnp.sum(state)

    findings = audit_donation(jax.jit(reduces, donate_argnums=(0,)),
                              jnp.ones((64, 64)), donate_argnums=(0,))
    assert findings and findings[0].code == "donation"
    assert findings[0].severity == "error"

    # identity-shaped carry aliases fully -> clean
    def carries(state):
        return state * 2.0

    assert audit_donation(jax.jit(carries, donate_argnums=(0,)),
                          jnp.ones((64, 64)), donate_argnums=(0,)) == []


def test_audit_flags_baked_scalar_const():
    lr = jnp.float32(0.1)  # a device scalar closed over -> baked literal

    def baked(x):
        return x * lr

    findings = audit_baked_consts(baked, jnp.ones(8))
    assert findings and findings[0].code == "baked-const"

    def threaded(x, lr):
        return x * lr

    assert audit_baked_consts(threaded, jnp.ones(8), jnp.float32(0.1)) == []


def test_audit_recompile_on_baked_operand():
    # config riding as STATIC pytree aux data — the realistic hazard: every
    # sweep setting bakes a fresh literal and forces a recompile
    @jax.tree_util.register_pytree_node_class
    class Cfg:
        def __init__(self, gamma):
            self.gamma = gamma

        def tree_flatten(self):
            return (), self.gamma

        @classmethod
        def tree_unflatten(cls, aux, _children):
            return cls(aux)

    def baked(x, cfg):
        return x * cfg.gamma

    findings = audit_recompile(baked, (jnp.ones(4), Cfg(0.1)),
                               (jnp.ones(4), Cfg(0.2)))
    assert findings and findings[0].code == "recompile"

    def traced(x, gamma):
        return x * gamma

    assert audit_recompile(
        traced, (jnp.ones(4), jnp.float32(0.1)),
        (jnp.ones(4), jnp.float32(0.2))
    ) == []


def test_audit_wire_flags_f32_smuggle():
    """A mixer that declares an int8 wire but ppermutes raw f32 must be
    reported as a dtype-widening leak."""
    script = """
import jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.analysis import audit_wire
from repro.comm.protocol import Mixer, trivial_comm_state
from repro.graphs import metropolis_weights, permutation_decomposition, ring_graph
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
decomp = permutation_decomposition(w)
pairs_per_matching = decomp.ppermute_pairs()

class SmugglingMixer(Mixer):
    '''Claims the int8 wire of its codec but sends full-precision floats.'''
    k = 8

    def __init__(self, mesh, specs):
        self.mesh, self.specs = mesh, specs

    def init_state(self, params):
        return trivial_comm_state()

    def wire_dtype_bytes(self, params):
        n = sum(x.size // self.k for x in jax.tree.leaves(params))
        m = len(pairs_per_matching)
        # declared: quantized payload + one f32 scale per node per matching
        return {"s8": float(n * self.k * m), "f32": float(4 * self.k * m)}

    def __call__(self, theta, state, *, round=None):
        sw = jnp.asarray(decomp.self_weights, jnp.float32)
        pws = [jnp.asarray(pw, jnp.float32)
               for pw in decomp.matching_weights]
        def body(t):
            i = jax.lax.axis_index("n")
            out = jax.tree.map(lambda x: x * sw[i], t)
            for pairs, pw in zip(pairs_per_matching, pws):
                recv = jax.tree.map(
                    lambda x: jax.lax.ppermute(x, "n", pairs), t)
                out = jax.tree.map(lambda o, r: o + pw[i] * r, out, recv)
            return out
        mixed = jax.shard_map(body, mesh=self.mesh,
                              in_specs=(self.specs,),
                              out_specs=self.specs)(theta)
        return mixed, state._replace(rounds=state.rounds + 1)

mesh = make_auto_mesh((k,), ("n",))
theta = {"a": jnp.zeros((k, 64), jnp.float32)}
specs = {"a": P("n", None)}
mixer = SmugglingMixer(mesh, specs)
findings = audit_wire(mixer, theta)
assert findings, "f32 smuggle not flagged"
assert any(f.code == "wire-dtype" and "widening" in f.message
           for f in findings), findings
print("OK")
"""
    _run_subprocess(script)


def test_audit_clean_on_shipped_trainer():
    """The dense fmnist-style train step passes every audit."""
    from repro.core import TrainerSpec

    spec = TrainerSpec(num_nodes=4, graph="ring", mu=3.0, robust=True,
                       lr=0.05, compress="int8")

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    trainer = spec.build(loss_fn)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (6, 2)) * 0.1}
    state = trainer.init(params)
    batch = (jnp.ones((4, 3, 6)), jnp.ones((4, 3, 2)))
    report = audit_train_step(trainer, state, batch)
    assert not report.errors, str(report)


def test_audit_clean_on_sanitized_trainer():
    """--sanitize checkify-wraps the step; the audit follows the transform."""
    from repro.core import TrainerSpec

    spec = TrainerSpec(num_nodes=4, graph="ring", mu=3.0, robust=True,
                       lr=0.05, compress="int8", sanitize=True)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    trainer = spec.build(loss_fn)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (6, 2)) * 0.1}
    state = trainer.init(params)
    batch = (jnp.ones((4, 3, 6)), jnp.ones((4, 3, 2)))
    report = audit_train_step(trainer, state, batch)
    assert not report.errors, str(report)


# -- sanitizer ------------------------------------------------------------------

def _tiny_trainer(sanitize, **kw):
    from repro.core import TrainerSpec

    spec = TrainerSpec(num_nodes=4, graph="ring", mu=3.0, robust=True,
                       lr=0.05, compress="int8", topology="dropout",
                       drop_p=0.3, sanitize=sanitize, **kw)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    trainer = spec.build(loss_fn)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (6, 2)) * 0.1}
    state = trainer.init(params)
    rng = np.random.default_rng(0)
    batches = (jnp.asarray(rng.normal(size=(5, 4, 3, 6)), jnp.float32),
               jnp.asarray(rng.normal(size=(5, 4, 3, 2)), jnp.float32))
    return trainer, state, batches


def test_sanitize_trajectory_bit_exact():
    runs = {}
    for sanitize in (False, True):
        trainer, state, batches = _tiny_trainer(sanitize)
        state, ms = trainer.run(state, batches)
        runs[sanitize] = (np.asarray(state.params["w"]),
                         np.asarray(ms["loss_mean"]))
    np.testing.assert_array_equal(runs[False][0], runs[True][0])
    np.testing.assert_array_equal(runs[False][1], runs[True][1])


def test_sanitize_throws_on_corrupted_w():
    trainer, state, batches = _tiny_trainer(True)
    target = trainer.mixer
    while hasattr(target, "inner"):
        target = target.inner
    sched = target.topo.schedule
    object.__setattr__(sched, "w",
                       jnp.asarray(sched.w).at[0, 0].add(0.5))
    with pytest.raises(Exception, match="doubly stochastic"):
        trainer.run(state, batches)


def test_sanitize_single_step_path():
    """jit=False/step path also discharges the checks (eager_run)."""
    trainer, state, batches = _tiny_trainer(True)
    batch = jax.tree.map(lambda x: x[0], batches)
    state2, ms = trainer.step(state, batch)
    assert int(state2.step) == 1


# -- adaptive EF re-base ---------------------------------------------------------

def test_adaptive_rebase_static_schedule_never_fires():
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm import CompressionConfig
from repro.core import make_gossip_mixer
from repro.dynamics import StaticSchedule
from repro.graphs import metropolis_weights, ring_graph
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("data",))
theta = {"a": jnp.asarray(np.random.default_rng(0).normal(size=(k, 64)),
                          jnp.float32)}
specs = {"a": P("data", None)}
cc = CompressionConfig(kind="int8", seed=0)
# huge threshold: the cond is never taken
adaptive = make_gossip_mixer(None, mesh, "data", specs, cc,
                             schedule=StaticSchedule(w),
                             ef_rebase_threshold=1e6)
# the pure delta wire
delta_only = make_gossip_mixer(None, mesh, "data", specs, cc,
                               schedule=StaticSchedule(w), ef_rebase_every=0)
st = adaptive.init_state(theta)
step = jax.jit(adaptive)
bits = []
for r in range(6):
    theta, st = step(theta, st)
    bits.append(float(st.wire_bits))
    assert float(st.ef_drift) >= 0.0
# never re-based: every round moves exactly the delta wire
d_bits = 8.0 * sum(delta_only.wire_dtype_bytes(theta).values())
assert all(b == d_bits for b in bits), (bits, d_bits)
# and the drift proxy stays tiny on a static schedule (cache never stale)
assert float(st.ef_drift) < 1.0, float(st.ef_drift)
print("OK")
"""
    _run_subprocess(script)


def test_adaptive_rebase_fires_under_dropout():
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.comm import CompressionConfig
from repro.core import make_gossip_mixer
from repro.dynamics import DropoutSchedule
from repro.graphs import metropolis_weights, ring_graph
from repro.launch.mesh import make_auto_mesh

k = 8
w = metropolis_weights(ring_graph(k))
mesh = make_auto_mesh((k,), ("data",))
theta = {"a": jnp.asarray(np.random.default_rng(0).normal(size=(k, 64)),
                          jnp.float32)}
specs = {"a": P("data", None)}
cc = CompressionConfig(kind="int8", seed=0)
mixer = make_gossip_mixer(None, mesh, "data", specs, cc,
                          schedule=DropoutSchedule(w, 0.4, seed=3),
                          ef_rebase_threshold=0.5)
st = mixer.init_state(theta)
step = jax.jit(mixer)
drifts, bits = [], []
for r in range(8):
    theta, st = step(theta, st)
    drifts.append(float(st.ef_drift))
    bits.append(float(st.wire_bits))
assert any(d > 0.5 for d in drifts), drifts   # the proxy moves under dropout
assert len(set(bits)) > 1, bits               # both round modes were taken
print("OK")
"""
    _run_subprocess(script)


# -- checkpoint schema padding ---------------------------------------------------

def test_comm_state_pad_table_covers_every_field():
    from repro.checkpoint.io import COMM_STATE_PAD

    assert set(COMM_STATE_PAD) == set(CommState._fields)


def test_pad_comm_fields_pads_and_rejects():
    from repro.checkpoint.io import _pad_comm_fields

    from repro.comm.protocol import trivial_comm_state

    # a pre-ef_drift checkpoint: stored tuple is one field short
    stored = tuple(trivial_comm_state())[:-1]
    padded = _pad_comm_fields(stored)
    assert len(padded) == len(CommState._fields)
    assert padded[-1] == ()
    restored = CommState(*padded)
    assert restored.ef_drift == ()
    # a FUTURE checkpoint (more fields than this build knows): refuse
    with pytest.raises(ValueError):
        _pad_comm_fields(tuple(trivial_comm_state()) + ((),))


# -- spec / CLI plumbing ---------------------------------------------------------

def test_spec_cli_threads_sanitize_and_threshold():
    import argparse

    from repro.core import TrainerSpec

    ap = argparse.ArgumentParser()
    TrainerSpec.add_cli_args(ap)
    args = ap.parse_args(["--sanitize", "--ef-rebase-threshold", "2.5"])
    spec = TrainerSpec.from_args(args, num_nodes=4, lr=0.1, graph="ring")
    assert spec.sanitize is True
    assert spec.ef_rebase_threshold == 2.5

    def loss_fn(p, b):
        return jnp.mean(p["w"] ** 2) + 0.0 * jnp.sum(b)

    trainer = spec.build(loss_fn)
    assert trainer.sanitize is True
