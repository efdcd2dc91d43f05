"""Multi-device (sharded) behaviour, run in subprocesses so the main pytest
process keeps a single CPU device (see conftest note / task spec)."""

import os
import subprocess
import sys

import pytest

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(script: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_gossip_equals_dense_mixing_on_mesh():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.graphs import ring_graph, erdos_renyi_graph, metropolis_weights, \
    permutation_decomposition
from repro.core import make_dense_mixer, make_gossip_mixer
mesh = make_auto_mesh((8,), ("data",))
for g in [ring_graph(8), erdos_renyi_graph(8, 0.5, seed=3)]:
    w = metropolis_weights(g)
    d = permutation_decomposition(w)
    theta = {"a": jnp.arange(8*4, dtype=jnp.float32).reshape(8,4),
             "b": jnp.ones((8,2,3)) * jnp.arange(8).reshape(8,1,1)}
    specs = {"a": P("data", None), "b": P("data", None, None)}
    dm = make_dense_mixer(w)
    gm = make_gossip_mixer(d, mesh, "data", specs)
    dense, _ = dm(theta, dm.init_state(theta))
    gossip, gst = jax.jit(gm)(theta, gm.init_state(theta))
    assert int(gst.rounds) == 1 and float(gst.wire_bits) > 0
    for k in theta:
        np.testing.assert_allclose(np.asarray(dense[k]), np.asarray(gossip[k]),
                                   rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_node_state_follows_param_sharding():
    """Per-node mixer state built from node-sharded parameters stays one
    node per device: the error-feedback copies (θ̂, s) and the gradient
    tracker.  Built on the default device, K full-width copies of them
    exhausted one chip's memory."""
    _run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.comm import CompressionConfig
from repro.core import make_dense_mixer, make_gossip_mixer
from repro.dynamics import LocalUpdateMixer
from repro.graphs import ring_graph, metropolis_weights, permutation_decomposition
from repro.launch.mesh import make_auto_mesh
k = 4
mesh = make_auto_mesh((k,), ("node",))
node = NamedSharding(mesh, P("node"))
params = jax.device_put({"w": jnp.ones((k, 8, 16)), "b": jnp.ones((k, 16))},
                        node)
specs = jax.tree.map(lambda _: P("node"), params)
w = metropolis_weights(ring_graph(k))
cc = CompressionConfig(kind="int8", seed=0)
ef = make_gossip_mixer(permutation_decomposition(w), mesh, "node", specs, cc)
st = ef.init_state(params)
gt = LocalUpdateMixer(make_dense_mixer(w), 2, gradient_tracking=True)
corr, _ = gt.init_state(params).track
for leaf in jax.tree.leaves((st.hat, st.hat_mix, corr)):
    assert leaf.sharding.is_equivalent_to(node, leaf.ndim), leaf.sharding
print("OK")
""", devices=4)


def test_gossip_multiaxis_node_dimension():
    """Node axis spanning ('pod','data') — the multi-pod configuration."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.graphs import ring_graph, metropolis_weights, permutation_decomposition
from repro.core import make_dense_mixer, make_gossip_mixer
mesh = make_auto_mesh((2, 4), ("pod", "data"))
g = ring_graph(8)
w = metropolis_weights(g)
d = permutation_decomposition(w)
theta = {"a": jnp.arange(8*6, dtype=jnp.float32).reshape(8, 6)}
specs = {"a": P(("pod", "data"), None)}
dm = make_dense_mixer(w)
gm = make_gossip_mixer(d, mesh, ("pod", "data"), specs)
dense, _ = dm(theta, dm.init_state(theta))
gossip, _ = jax.jit(gm)(theta, gm.init_state(theta))
np.testing.assert_allclose(np.asarray(dense["a"]), np.asarray(gossip["a"]),
                           rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_sharded_drdsgd_step_matches_single_device():
    """The pjit'd DR-DSGD step on an 8-device mesh == unsharded result."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.core import RobustConfig, TrainStepConfig, build_train_step, \
    make_dense_mixer
from repro.core.drdsgd import init_state, replicate_params
from repro.graphs import ring_graph, metropolis_weights
from repro.optim import sgd

k = 8
w = metropolis_weights(ring_graph(k))
def loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)
mixer = make_dense_mixer(w)
step = build_train_step(loss_fn, sgd(0.05), mixer,
                        TrainStepConfig(robust=RobustConfig(mu=2.0)))
params = {"w": jnp.ones((5, 3)) * 0.1, "b": jnp.zeros((3,))}
state = init_state(replicate_params(params, k), sgd(0.05), mixer=mixer)
rng = np.random.default_rng(0)
batch = (jnp.asarray(rng.normal(size=(k, 4, 5)), jnp.float32),
         jnp.asarray(rng.normal(size=(k, 4, 3)), jnp.float32))
ref_state, ref_metrics = jax.jit(step)(state, batch)

mesh = make_auto_mesh((8,), ("data",))
sh = lambda *spec: NamedSharding(mesh, P(*spec))
pspecs = {"w": P("data", None, None), "b": P("data", None)}
comm_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       mixer.state_specs(pspecs),
                       is_leaf=lambda x: isinstance(x, P))
state_sh = type(state)(
    params={"w": sh("data", None, None), "b": sh("data", None)},
    opt_state=(), step=sh(), comm=comm_sh)
batch_sh = (sh("data", None, None), sh("data", None, None))
jstep = jax.jit(step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None))
sh_state, sh_metrics = jstep(state, batch)
for a, b in zip(jax.tree.leaves(ref_state.params),
                jax.tree.leaves(sh_state.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)
np.testing.assert_allclose(float(ref_metrics["loss_mean"]),
                           float(sh_metrics["loss_mean"]), rtol=1e-5)
print("OK")
""")


def test_hierarchical_mixer_with_replica_axis():
    """FSDP-inside/gossip-across: replica-synced params stay identical and
    node mixing matches dense mixing."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.graphs import ring_graph, metropolis_weights, permutation_decomposition
from repro.core import make_dense_mixer, make_gossip_mixer
mesh = make_auto_mesh((4, 2), ("node", "replica"))
g = ring_graph(4)
w = metropolis_weights(g)
d = permutation_decomposition(w)
theta = {"a": jnp.arange(4*6, dtype=jnp.float32).reshape(4, 6)}
specs = {"a": P("node", None)}   # replicated over "replica"
mixer = make_gossip_mixer(d, mesh, "node", specs, replica_axis="replica")
dm = make_dense_mixer(w)
dense, _ = dm(theta, dm.init_state(theta))
out, _ = jax.jit(mixer)(theta, mixer.init_state(theta))
np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(dense["a"]),
                           rtol=1e-5, atol=1e-6)
print("OK")
""")


def test_smoke_arch_trains_on_mesh():
    """A smoke LM runs one sharded decentralized step on a 4x2 mesh."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_auto_mesh
from repro.configs import get_arch
from repro.core import RobustConfig, TrainStepConfig, build_train_step, \
    make_dense_mixer
from repro.core.drdsgd import init_state, replicate_params
from repro.graphs import ring_graph, metropolis_weights
from repro.models import TransformerLM
from repro.optim import sgd

cfg = get_arch("qwen2_0_5b", smoke=True)
model = TransformerLM(cfg)
mesh = make_auto_mesh((4, 2), ("data", "model"))
k = 4
w = metropolis_weights(ring_graph(k))
mixer = make_dense_mixer(w)
step = build_train_step(model.loss, sgd(1e-2), mixer,
                        TrainStepConfig(robust=RobustConfig(mu=6.0)))
params = model.init(jax.random.PRNGKey(0))
state = init_state(replicate_params(params, k), sgd(1e-2), mixer=mixer)
pspecs = model.param_specs(mesh, mode="train", node_axis="data")
state_sh = type(state)(
    params=jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P)),
    opt_state=(), step=NamedSharding(mesh, P()),
    comm=jax.tree.map(lambda s: NamedSharding(mesh, s),
                      mixer.state_specs(pspecs),
                      is_leaf=lambda x: isinstance(x, P)))
toks = jax.random.randint(jax.random.PRNGKey(1), (k, 2, 33), 0, cfg.vocab)
batch = {"tokens": toks}
batch_sh = {"tokens": NamedSharding(mesh, P("data", None, None))}
jstep = jax.jit(step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None))
new_state, metrics = jstep(state, batch)
assert np.isfinite(float(metrics["loss_mean"]))
assert int(new_state.step) == 1
print("OK", float(metrics["loss_mean"]))
""")
