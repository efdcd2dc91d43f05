"""The general runner of a training job (``kind: train``).

The job's traffic file states how its K nodes sit on the cell's chips and
how they mix, and the runner builds exactly that or stops with an error:

* ``lowering: dense`` -- the K nodes stacked on one chip and mixed by one
  dense einsum of the graph's ``mixing`` matrix (``chips`` 1, ``compress``
  ``none``);
* ``lowering: gossip`` -- one node per chip (``nodes`` equal to the cell's
  ``chips``) on a ``("node",)`` mesh, mixed by one collective-permute per
  matching of the matrix's permutation decomposition.  ``compress: int8``
  sends CHOCO error-feedback innovations over a stochastically rounded int8
  wire with one scale per ``block_d``-element block (``error_feedback``
  must be true; ``gamma`` the correction's step; ``use_kernel`` serves the
  codec by the Pallas ``quant_gossip`` kernels).

Set-up builds one trainer and its state from the seed's weights and drives
it through its first segments with the window's own call
(``DecentralizedTrainer.run`` on a stacked segment of the token feed): that
compiles the segment program and gives the numbers that the reference
checks.  The window then runs whole segments, each sampled on the host while
the device runs the one before, until ``--seconds`` have passed (a traced
run: at most the job's ``trace_seconds``, where it states one), and ends in
``block_until_ready``.

``correct`` compares those first segments with the plain reference
(``bench/reference``), on the same weights and rows:

* uncompressed mixing -- one segment: each step's mean and worst node loss,
  and each parameter leaf's change over the segment, node by node;
* the compressed wire -- two one-step segments, each checked from the
  program's parameters before it: the step's mean and worst node loss, each
  leaf's change of the node mean (which the round leaves exact), and the
  round's public copies and correction against the wire's rounding
  (``bench/reference/choco.py``).
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, program
from bench.traffic.gen import TokenFeed
from bench.weights import flatten, make_params, nest


def _refuse(job: dict, why: str):
    raise ValueError(f"the training runner cannot honour this job ({why}): "
                     f"{ {k: job.get(k) for k in ('lowering', 'compress', 'nodes')} }")


def compressed(job: dict) -> bool:
    return job["compress"] != "none"


def compression(job: dict, seed: int):
    """The job's wire as the program's ``CompressionConfig`` (None: f32)."""
    if not compressed(job):
        return None
    from repro.comm import CompressionConfig

    return CompressionConfig(kind=job["compress"],
                             error_feedback=job["error_feedback"],
                             use_kernel=job["use_kernel"],
                             block_d=job["block_d"], gamma=job["gamma"],
                             seed=program.program_seed(seed))


def validate(spec: common.Spec) -> None:
    from repro.core import RobustConfig

    job = spec.job
    if RobustConfig().loss_clip != job["loss_clip"]:
        _refuse(job, "its loss_clip is not the one the trainer uses")
    if job["optimizer"] != "sgd":
        _refuse(job, "the runner drives SGD only")
    if job["lowering"] == "dense":
        if compressed(job) or spec.chips != 1:
            _refuse(job, "dense mixing stacks the nodes on one chip, uncompressed")
    elif job["lowering"] == "gossip":
        if job["nodes"] != spec.chips:
            _refuse(job, "gossip places one node per chip")
        if job["compress"] not in ("none", "int8"):
            _refuse(job, "the wire is f32 or int8")
        if compressed(job) and not (job["error_feedback"]
                                    and job["segment_steps"] == 1):
            _refuse(job, "the int8 wire is checked with error feedback, one "
                         "step a segment")
    else:
        _refuse(job, "lowering is dense or gossip")


# -- one node per chip --------------------------------------------------------------

def node_mesh(spec: common.Spec, devs):
    """The ``("node",)`` mesh of a gossip job, one node per chip (else None)."""
    if spec.job["lowering"] != "gossip":
        return None
    from repro.launch.mesh import make_auto_mesh

    return make_auto_mesh((len(devs),), ("node",), devices=devs)


def node_sharded(tree, mesh):
    """Each node-stacked leaf split over the mesh's nodes; the rest replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = mesh.shape["node"]

    def put(x):
        spec = P("node") if getattr(x, "ndim", 0) and x.shape[0] == k else P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def one_node_per_device(params, mesh) -> None:
    """Raise unless every leaf holds one node on each device of the mesh."""
    devices = {d.id for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        if ({s.device.id for s in shards} != devices
                or any(s.data.shape[0] != 1 for s in shards)
                or len({s.index[0].start for s in shards}) != len(devices)):
            raise RuntimeError("the nodes are not one to a device: "
                               f"{[(s.device.id, s.index) for s in shards]}")


def _placer(mesh, devs):
    """How a stacked segment (seg, K, batch, seq + 1) goes to the chips."""
    if mesh is None:
        return jnp.asarray
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(None, "node"))
    return lambda seg: jax.device_put(seg, sharding)


def _gossip_mixer(job, mesh, model, cc, make=None):
    from jax.sharding import PartitionSpec as P

    from repro.core import make_gossip_mixer
    from repro.graphs import (build_graph, metropolis_weights,
                              permutation_decomposition)

    if job["mixing"] != "metropolis":
        _refuse(job, "gossip mixes by the Metropolis matrix")
    decomp = permutation_decomposition(
        metropolis_weights(build_graph(job["graph"], job["nodes"])))
    specs = jax.tree.map(lambda _: P("node"), model.param_shapes())
    make = make or (lambda d, m, s, c: make_gossip_mixer(d, m, "node", s, c))
    return make(decomp, mesh, specs, cc)


def build(spec: common.Spec, model, hooks=None, mesh=None):
    from repro.core import TrainerSpec

    validate(spec)
    job = spec.job
    hooks = hooks or {}
    cc = compression(job, spec.seed)
    ts = TrainerSpec(num_nodes=job["nodes"], graph=job["graph"],
                     mixing=job["mixing"], mu=job["mu"], lr=job["lr"],
                     grad_clip=job["grad_clip"], compress=cc or "none",
                     seed=program.program_seed(spec.seed))
    loss_fn = hooks["loss"](model) if "loss" in hooks else model.loss
    if "mixer" in hooks:
        mixer = hooks["mixer"](job)
    elif mesh is not None:
        mixer = _gossip_mixer(job, mesh, model, cc, hooks.get("gossip"))
    else:
        mixer = None
    trainer = ts.build(loss_fn, mixer=mixer)
    if "trainer" in hooks:
        hooks["trainer"](trainer)
    return trainer


# -- set-up -------------------------------------------------------------------------

def _leaf_change(params, params0):
    """{leaf: (K,) norm of theta_k - theta_0}."""
    flat, flat0 = flatten(params), flatten(params0)
    return {k: jnp.sqrt(jnp.sum(jnp.square(flat[k] - flat0[k][None]),
                                axis=tuple(range(1, flat[k].ndim))))
            for k in flat}


def _host(tree) -> dict:
    """A host copy of a node-stacked tree, flattened."""
    return {n: np.asarray(v) for n, v in flatten(tree).items()}


def start(spec: common.Spec, devs, hooks=None, trainer=None, phases=None,
          t_start: float = 0.0, mesh=None):
    """Set-up up to the window: the trainer (built, or the one given), its
    state from the seed's weights, the feed, and the first segments driven
    through the window's own call.  Returns (trainer, state, feed, place,
    first): ``place`` puts a sampled segment on the chips, ``first`` holds
    what the reference checks."""
    cfg, job = spec.cfg, spec.job
    k = job["nodes"]
    model = program.model(cfg)
    if trainer is None:
        trainer = build(spec, model, hooks, mesh)
    # uncommitted for a mesh, which the replication below lays out
    params0 = make_params(cfg, spec.seed, device=devs[0] if mesh is None else None)
    if mesh is None:
        # committed to the chip like the state each segment returns, so that
        # the first segment's program is the one every later segment runs
        state = jax.device_put(trainer.init(params0), devs[0])
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # every node starts at the same point, built on its own device
        stacked = jax.jit(
            lambda p: jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), p),
            out_shardings=NamedSharding(mesh, P("node")))(params0)
        state = node_sharded(trainer.init_stacked(stacked), mesh)
        del stacked
        if compressed(job):
            del params0
        else:
            params0 = jax.device_put(params0, NamedSharding(mesh, P()))
    place = _placer(mesh, devs)
    feed = TokenFeed(job, vocab=cfg["vocab_size"], seed=spec.seed)
    if phases is not None:
        jax.block_until_ready(state)
        phases["weights_state_feed"] = common.now() - t_start
    if not compressed(job):
        seg0 = feed.segment(0)
        state, ms = trainer.run(state, {"tokens": place(seg0)})
        first = {"segs": [seg0],
                 "loss_mean": np.asarray(ms["loss_mean"], np.float64),
                 "loss_worst": np.asarray(ms["loss_worst"], np.float64),
                 "change": {n: np.asarray(v, np.float64) for n, v in
                            jax.jit(_leaf_change)(state.params, params0).items()}}
    else:
        first = {"segs": [], "loss_mean": [], "loss_worst": [], "theta": [],
                 "hat": []}
        for i in range(2):
            seg = feed.segment(i)
            state, ms = trainer.run(state, {"tokens": place(seg)})
            first["segs"].append(seg)
            for key in ("loss_mean", "loss_worst"):
                first[key].append(float(np.asarray(ms[key])[0]))
            first["theta"].append(_host(state.params))
            hat = state.comm.hat
            first["hat"].append(_host(hat) if hat != () else None)
        first["loss_mean"] = np.asarray(first["loss_mean"])
        first["loss_worst"] = np.asarray(first["loss_worst"])
    if mesh is not None:
        one_node_per_device(state.params, mesh)
    return trainer, state, feed, place, first


# -- the run ------------------------------------------------------------------------

def run(spec: common.Spec, devs, t_start: float, hooks=None):
    from repro.obs import RecompileWatchdog

    job = spec.job
    k, b, s, seg = (job["nodes"], job["batch_per_node"], job["seq_len"],
                    job["segment_steps"])
    phases = {"start": common.now() - t_start}
    mesh = node_mesh(spec, devs)
    trainer = build(spec, program.model(spec.cfg), hooks, mesh)
    watch = RecompileWatchdog(label=spec.workload)
    watch.track("run", trainer._run, allowed=1)
    trainer, state, feed, place, first = start(
        spec, devs, trainer=trainer, phases=phases, t_start=t_start, mesh=mesh)
    n_first = len(first["segs"])
    setup_s = common.now() - t_start
    # a traced run's window is at most the job's trace_seconds: a profile of
    # four chips takes several seconds to read back for each second traced
    seconds = spec.seconds
    if spec.trace and "trace_seconds" in job:
        seconds = min(seconds, job["trace_seconds"])

    # the window: whole segments, the next sampled while the device runs one
    losses, n_seg = [], 0
    with common.traced(spec.trace) as tr:
        t0 = common.now()
        pending = None
        while True:
            with common.span("bench:sample"):
                batch = {"tokens": place(feed.segment(n_first + n_seg))}
            with common.span("bench:dispatch"):
                state, ms = trainer.run(state, batch)
            if pending is not None:
                with common.span("bench:wait"):
                    losses.append(np.asarray(pending))
            pending = ms["loss_mean"]
            n_seg += 1
            if common.now() - t0 >= seconds:
                break
        with common.span("bench:wait"):
            losses.append(np.asarray(pending))
            jax.block_until_ready(state.params)
        t1 = common.now()
    window_s = t1 - t0
    steps = n_seg * seg
    tokens = steps * k * b * s
    progs = watch.check()
    device = common.device_record(devs)
    del state, trainer, ms, pending, batch
    gc.collect()

    t_check = common.now()
    gaps = check(spec, first, devs, mesh)
    check_s = common.now() - t_check
    lim = spec.limits
    checks = {name: common.check(gaps[name], lim[name]) for name in lim}
    loss_all = np.concatenate(losses)
    failed = int(np.sum(~np.isfinite(loss_all)))
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and failed == 0 and progs["run"] == 1

    result = {"correct": bool(correct), "attempted": int(steps),
              "failed": failed, "device": device}
    if not spec.trace:
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens / window_s, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        ctx = common.Context(spec=spec, trace=tr.trace, devices=[d.id for d in devs],
                             window=tr.trace.window(), window_s=window_s,
                             counts={"steps": steps, "tokens": tokens})
        result["metrics"] = common.read_per_layer(spec, ctx)
        busy, win = common.busy_and_window(ctx)
        result["device"].update(busy_s=busy, window_s=win)
        result["breakdown"] = common.breakdown(ctx)
    result["log"] = {"setup_s": setup_s, "window_s": window_s,
                     "segments": n_seg, "programs": progs,
                     "first_loss_mean": np.asarray(first["loss_mean"]).tolist(),
                     "setup_phases_s": phases,
                     "check_s": check_s, "worst_leaf": max(
                         gaps["per_leaf"], key=gaps["per_leaf"].get)}
    return result, checks


def check(spec: common.Spec, first: dict, devs, mesh) -> dict:
    """The numbers compared, for the job's kind of mixing."""
    if compressed(spec.job):
        ref = choco_reference(spec.cfg, spec.job, spec.seed, first, mesh)
        return compare_choco(first, ref)
    ref = reference_run(spec.cfg, spec.job, spec.seed, first["segs"][0], devs[0])
    return compare(first, ref)


# -- the check of uncompressed mixing -----------------------------------------------

def reference_run(cfg, job, seed, seg0, device, dtype=jnp.float32, fault=None):
    """The reference job over the segment's steps, from the seed's weights.

    ``fault`` plants one of the faults the check must catch: ``half_batch``
    (each node's loss over the first half of its positions) or ``no_mix``
    (the exchange between nodes left out).  Returns per-step node losses,
    each leaf's first-step gradient norm (largest over the nodes) and each
    leaf's change per node over the segment.
    """
    from bench.reference import drdsgd

    k = job["nodes"]
    w = drdsgd.metropolis(job["graph"], k) if fault != "no_mix" else np.eye(k)
    seg0 = np.asarray(seg0)
    if fault == "half_batch":
        seg0 = seg0[..., : job["seq_len"] // 2 + 1]
    with jax.default_matmul_precision("highest"):
        p0 = make_params(cfg, seed, dtype=dtype, device=device)
        grad = jax.jit(lambda p, r: drdsgd.node_grad(cfg, job, p, r, dtype))
        sgd = jax.jit(lambda p, g, sc: jax.tree.map(
            lambda x, y: x - (job["lr"] * sc).astype(x.dtype) * y, p, g),
            donate_argnums=(0,))
        nodes = [jax.tree.map(jnp.copy, p0) for _ in range(k)]
        losses, first_g = [], None
        for t in range(seg0.shape[0]):
            ls = []
            for i in range(k):
                l, g, sc = grad(nodes[i], jnp.asarray(seg0[t, i]))
                if t == 0:
                    gn = {n: float(jnp.linalg.norm(v.astype(jnp.float32)))
                          for n, v in flatten(g).items()}
                    first_g = gn if first_g is None else {
                        n: max(first_g[n], gn[n]) for n in gn}
                nodes[i] = sgd(nodes[i], g, sc)
                ls.append(float(l))
                del g
            losses.append(ls)
            nodes = _mix(nodes, w)
        change = {}
        flat0 = flatten(p0)
        for i in range(k):
            for n, v in flatten(nodes[i]).items():
                d = float(jnp.linalg.norm((v.astype(jnp.float32)
                                           - flat0[n].astype(jnp.float32))))
                change.setdefault(n, np.zeros(k))[i] = d
    return {"losses": np.asarray(losses, np.float64), "first_grad": first_g,
            "change": change}


def _mix(nodes, w):
    """theta_i <- sum_j W_ij theta_j, leaf by leaf."""
    k = len(nodes)
    flats = [flatten(n) for n in nodes]
    out = [dict() for _ in range(k)]
    for name in flats[0]:
        leaves = [f[name] for f in flats]
        for i in range(k):
            acc = sum(float(w[i, j]) * leaves[j].astype(jnp.float32)
                      for j in range(k) if w[i, j] != 0.0)
            out[i][name] = acc.astype(leaves[i].dtype)
        for f in flats:
            f[name] = None
    return [nest(o) for o in out]


def _loss_gap(prog: dict, losses: np.ndarray) -> float:
    """Over the steps, the largest of |program - reference| / reference for
    the mean and the worst node loss; ``losses`` is (steps, K)."""
    gaps = [np.abs(prog["loss_mean"] - losses.mean(axis=1)) / np.abs(losses.mean(axis=1)),
            np.abs(prog["loss_worst"] - losses.max(axis=1)) / np.abs(losses.max(axis=1))]
    return float(np.max(np.concatenate(gaps)))


def _kept(first_grad: dict) -> list:
    """Leaves whose reference first-step gradient is at least a thousandth
    of the median leaf's (a key bias under softmax is not)."""
    med = float(np.median(list(first_grad.values())))
    return [n for n in first_grad if first_grad[n] >= 1e-3 * med]


def _change_gaps(prog: dict, ref: dict, kept: list) -> dict:
    """{leaf: largest gap between the program's and the reference's norm of
    the leaf's change, against the larger of that leaf's reference norm and
    the median leaf's}; each value an array over nodes or steps."""
    med = float(np.median([np.median(ref[n]) for n in kept]))
    return {n: float(np.max(np.abs(prog[n] - ref[n]) / np.maximum(ref[n], med)))
            for n in kept}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, as gaps of the program from the reference.

    * ``loss_rel_gap``: over the steps, the largest of |program - reference|
      / reference for the mean and the worst node loss.
    * ``change_rel_gap``: over the leaves and nodes, the largest gap between
      the program's and the reference's norm of a leaf's change, against the
      larger of that leaf's reference norm and the median leaf's.  Leaves
      whose reference first-step gradient is under a thousandth of the
      median leaf's (a key bias under softmax) are left out.
    """
    kept = _kept(ref["first_grad"])
    per_leaf = _change_gaps(prog["change"], ref["change"], kept)
    return {"loss_rel_gap": _loss_gap(prog, ref["losses"]),
            "change_rel_gap": max(per_leaf.values()),
            "leaves_left_out": sorted(set(ref["first_grad"]) - set(kept)),
            "per_leaf": per_leaf}


# -- the check of the compressed wire -----------------------------------------------

def choco_reference(cfg, job, seed, first, mesh):
    """The reference's side of the compressed-wire check, step by step from
    the program's parameters before each step (the seed's weights, then the
    program's after its first step), every node at once, node i where the
    program keeps it.

    Per step: each node's loss, and per leaf the norms of the step's change
    of the node mean (the program's, and the reference's DR-SGD step) and
    the round's sums of squares (``choco.round_stats``) of the program's
    public copies and post-mix parameters against the pre-mix parameters the
    reference computes.  The previous public copies are the program's own:
    with stochastic rounding, copies that the reference made would differ
    from the program's in every element, and the next round's innovation
    with them."""
    stat = round_stat(job)
    rounds, losses, first_g = [], [], None
    with jax.default_matmul_precision("highest"):
        step = pre_mix_step(cfg, job, jnp.float32)
        for t, seg in enumerate(first["segs"]):
            start = (seed_weights(cfg, job, seed, mesh, jnp.float32) if t == 0
                     else nest({n: node_sharded(v, mesh)
                                for n, v in first["theta"][t - 1].items()}))
            ls, pre, norms = step(start, node_sharded(np.asarray(seg)[0], mesh))
            losses.append(np.asarray(ls, np.float64))
            if t == 0:
                first_g = {n: float(jnp.max(v)) for n, v in norms.items()}
            pre, start = flatten(pre), flatten(start)
            stats = {}
            for leaf in pre:
                if first["hat"][t] is None:
                    stats[leaf] = None
                    continue
                rows = lambda a: node_rows(node_sharded(a, mesh))  # noqa: E731
                x = node_rows(pre[leaf])
                hp = (rows(first["hat"][t - 1][leaf]) if t
                      else jnp.zeros_like(x))
                stats[leaf] = leaf_stats(stat, x, hp, rows(first["hat"][t][leaf]),
                                         rows(first["theta"][t][leaf]),
                                         node_rows(start[leaf]))
            rounds.append(stats)
            del pre, start
    return {"losses": np.asarray(losses, np.float64), "first_grad": first_g,
            "rounds": rounds}


def round_stat(job):
    """The jitted statistics of one leaf's round: (``choco.round_stats``, the
    norm of the node mean's change after the round, before it)."""
    from bench.reference import choco, drdsgd

    w = jnp.asarray(drdsgd.metropolis(job["graph"], job["nodes"]), jnp.float32)
    return jax.jit(lambda x, hp, hn, xn, s: (
        choco.round_stats(x, hp, hn, xn, w, gamma=job["gamma"],
                          block_d=job["block_d"], qmax=127.0),
        jnp.linalg.norm(jnp.mean(xn - s, axis=0)),
        jnp.linalg.norm(jnp.mean(x - s, axis=0))))


def leaf_stats(stat, x, hp, hn, xn, start) -> dict:
    sums, prog_c, ref_c = stat(x, hp, hn, xn, start)
    return {**{a: float(v) for a, v in sums.items()},
            "prog_change": float(prog_c), "ref_change": float(ref_c)}


def pre_mix_step(cfg, job, dtype):
    """The reference's DR-SGD step of every node at once (``_pre_mix``)."""
    return jax.jit(jax.vmap(lambda p, r: _pre_mix(cfg, job, p, r, dtype)))


def seed_weights(cfg, job, seed, mesh, dtype):
    """The seed's weights in ``dtype``, one copy per node on the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = job["nodes"]
    return jax.jit(lambda p: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), p),
        out_shardings=NamedSharding(mesh, P("node")))(
            make_params(cfg, seed, dtype=dtype))


def node_rows(a):
    """A node-stacked leaf as (K, d) float32."""
    return a.reshape(a.shape[0], -1).astype(jnp.float32)


def _pre_mix(cfg, job, p, rows, dtype):
    """(loss, pre-mix parameters, gradient norm per leaf) of one node."""
    from bench.reference import drdsgd

    l, g, sc = drdsgd.node_grad(cfg, job, p, rows, dtype)
    pre = jax.tree.map(lambda x, y: x - (job["lr"] * sc).astype(x.dtype) * y, p, g)
    norms = {n: jnp.linalg.norm(v.astype(jnp.float32)) for n, v in flatten(g).items()}
    return l, pre, norms


def compare_choco(prog: dict, ref: dict) -> dict:
    """The numbers compared for the compressed wire, over its first rounds.

    * ``loss_rel_gap``: as for uncompressed mixing, each step's losses at
      the program's parameters before it.
    * ``change_rel_gap``: over the leaves and steps, the gap between the
      program's and the reference's norm of the step's change of the node
      mean of a leaf, against the larger of that leaf's reference norm and
      the median leaf's (leaves left out as for uncompressed mixing).
    * ``wire_rms_ratio``: over the leaves and rounds, the largest
      rms(h' - x) / rms(s) (``bench/reference/choco.py``).
    * ``choco_rms_ratio``: the same of rms(x' - x - gamma (W h' - h')).
    A program that keeps no public copies reads infinity on both.
    """
    kept = _kept(ref["first_grad"])
    rounds = ref["rounds"]
    change = {n: np.asarray([r[n]["prog_change"] if r[n] else np.inf for r in rounds])
              for n in kept}
    ref_change = {n: np.asarray([r[n]["ref_change"] if r[n] else 0.0 for r in rounds])
                  for n in kept}
    per_leaf = _change_gaps(change, ref_change, kept)

    def ratio(key):
        return max(np.sqrt(r[n][key] / r[n]["quanta"]) if r[n] else np.inf
                   for r in rounds for n in r)

    return {"loss_rel_gap": _loss_gap(prog, ref["losses"]),
            "change_rel_gap": max(per_leaf.values()),
            "wire_rms_ratio": float(ratio("wire")),
            "choco_rms_ratio": float(ratio("choco")),
            "leaves_left_out": sorted(set(ref["first_grad"]) - set(kept)),
            "per_leaf": per_leaf}
