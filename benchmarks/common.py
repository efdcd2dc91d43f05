"""Shared benchmark helpers: a timed decentralized training run with the
paper's evaluation protocol (avg / worst-distribution accuracy, node STDEV).

The training loop drives ``DecentralizedTrainer.run`` — the scan-compiled
multi-step driver — in segments of ``eval_every`` steps, so benchmarks
measure the compiled hot path (one program per segment, state donated)
rather than per-step Python dispatch."""

from __future__ import annotations

import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TrainerSpec
from repro.obs import RecompileWatchdog
from repro.data import (
    make_cifar_like,
    make_fmnist_like,
    pathological_noniid_partition,
)
from repro.models import cnn_apply, cnn_init, mlp_apply, mlp_init
from repro.models.paper_nets import make_classifier_loss


def make_task(dataset: str, num_nodes: int, seed: int = 0):
    if dataset == "fmnist":
        ds = make_fmnist_like(n_train=4000, n_test=600, seed=0)
        init_fn, apply_fn = mlp_init, mlp_apply
    elif dataset == "cifar":
        ds = make_cifar_like(n_train=3000, n_test=500, seed=1)
        init_fn, apply_fn = cnn_init, cnn_apply
    else:
        raise ValueError(dataset)
    fed = pathological_noniid_partition(ds, num_nodes, shards_per_node=2,
                                        seed=seed)
    return fed, init_fn, apply_fn


def stack_batches(fed, rng, batch: int, n: int):
    """Sample ``n`` per-node batches and stack them along a time axis."""
    xs, ys = zip(*[fed.sample_batch(rng, batch) for _ in range(n)])
    return jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))


def params_digest(params) -> str:
    """sha256 over the raw bytes of every param leaf (bit-exactness checks)."""
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _gossip_mixer(graph, kwargs, num_nodes, topology, drop_p, seed,
                  compression, ef_rebase_every, ef_rebase_threshold=0.0):
    """Build the ppermute gossip lowering of a dynamic topology (needs
    ``jax.device_count() >= num_nodes``: one node per device shard).

    Returns ``(make, put_state)``: ``make(params_tree)`` builds the mixer
    for that tree's structure, and ``put_state`` pins a freshly-initialized
    DecentralizedState onto the mesh shardings so every ``run()`` segment
    reuses ONE compiled program (an unpinned first segment would compile a
    second program for the resharded carry).
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.comm import make_gossip_mixer
    from repro.dynamics import make_schedule
    from repro.graphs import build_graph, metropolis_weights
    from repro.launch.mesh import make_auto_mesh

    if jax.device_count() < num_nodes:
        raise RuntimeError(
            f"the gossip lowering needs >= {num_nodes} devices (got "
            f"{jax.device_count()}); set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_nodes} before "
            "importing jax (benchmarks/fig9_dynamics.py does)")
    mesh = make_auto_mesh((num_nodes,), ("node",))
    w = metropolis_weights(build_graph(graph, num_nodes, **kwargs))
    schedule = make_schedule(topology, w=w, k=num_nodes, drop_p=drop_p,
                             seed=seed)

    def make(params_tree):
        param_specs = jax.tree.map(lambda _: P("node"), params_tree)
        return make_gossip_mixer(None, mesh, "node", param_specs,
                                 compression, schedule=schedule,
                                 ef_rebase_every=ef_rebase_every,
                                 ef_rebase_threshold=ef_rebase_threshold)

    def put_state(state):
        def _put(x):
            if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1 \
                    and x.shape[0] == num_nodes:
                return jax.device_put(x, NamedSharding(mesh, P("node")))
            return jax.device_put(x, NamedSharding(mesh, P()))
        return jax.tree.map(_put, state)

    return make, put_state


def _hierarchical_mixer(graph, kwargs, num_nodes, replicas, seed):
    """Build the hierarchical psum-then-gossip lowering: ``num_nodes`` ×
    ``replicas`` mesh, params node-stacked over ``node`` and replicated over
    ``replica`` (the FSDP-inside / gossip-across shape — K ≪ world size, so
    the consensus wire scales with K, not the device count).

    Returns ``(make, put_state)`` with the same contract as
    :func:`_gossip_mixer`.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.comm import make_gossip_mixer
    from repro.graphs import (
        build_graph,
        metropolis_weights,
        permutation_decomposition,
    )
    from repro.launch.mesh import make_auto_mesh

    if jax.device_count() < num_nodes * replicas:
        raise RuntimeError(
            f"the hierarchical lowering needs >= {num_nodes * replicas} "
            f"devices (got {jax.device_count()})")
    mesh = make_auto_mesh((num_nodes, replicas), ("node", "replica"))
    w = metropolis_weights(build_graph(graph, num_nodes, **kwargs))
    decomp = permutation_decomposition(w)

    def make(params_tree):
        param_specs = jax.tree.map(lambda _: P("node"), params_tree)
        return make_gossip_mixer(decomp, mesh, "node", param_specs,
                                 replica_axis="replica")

    def put_state(state):
        def _put(x):
            if hasattr(x, "shape") and getattr(x, "ndim", 0) >= 1 \
                    and x.shape[0] == num_nodes:
                return jax.device_put(x, NamedSharding(mesh, P("node")))
            return jax.device_put(x, NamedSharding(mesh, P()))
        return jax.tree.map(_put, state)

    return make, put_state


def run_decentralized(dataset: str, *, robust: bool, mu: float = 6.0,
                      num_nodes: int = 10, steps: int = 150, batch: int = 32,
                      graph: str = "erdos_renyi", p: float = 0.3,
                      lr: float | None = None, seed: int = 0,
                      eval_every: int = 25,
                      grad_clip: float | None = 2.0,
                      lr_compensate: bool = True,
                      compression=None,
                      topology: str = "static", drop_p: float = 0.0,
                      local_updates: int = 1,
                      gradient_tracking: bool = False,
                      straggler_p: float = 0.0,
                      outage_p: float = 0.0,
                      lowering: str = "dense",
                      replicas: int = 2,
                      ef_rebase_every: int = 8,
                      ef_rebase_threshold: float = 0.0,
                      sanitize: bool = False,
                      audit: bool = False,
                      obs=None) -> dict:
    """One (DR-)DSGD training run; returns metrics + eval history + timing.

    ``lr_compensate`` equalizes the *initial* effective step size across
    algorithms: DR-DSGD's update is η·exp(ℓ̄/μ)·g/μ, so at the untrained
    loss ℓ₀ = log(C) we scale η by μ/exp(ℓ₀/μ). Without this, comparisons
    at short horizons measure the LR mismatch, not the DRO weighting (the
    paper tunes a single η per experiment on converged real-data runs;
    see EXPERIMENTS.md §Paper-repro).

    ``lowering="gossip"`` runs the consensus on the ppermute lowering
    (``make_gossip_mixer(..., schedule=...)`` — one node per device shard):
    memoryless masked int8 wire for ``error_feedback=False`` configs, the
    error-feedback wire with ``hat_mix`` re-basing every
    ``ef_rebase_every`` rounds otherwise.

    ``obs`` (a :class:`repro.obs.MetricsSink`) streams the per-step train
    tap.  Every run is guarded by a :class:`repro.obs.RecompileWatchdog` on
    the compiled scan driver — one program per configuration, +1 tolerated
    for a ragged final segment — so each fig benchmark asserts the
    zero-recompile invariant for free (``RecompileError`` on violation).

    ``sanitize`` checkify-wraps the step with the runtime invariant checks
    of ``repro.analysis.sanitize`` (bit-exact trajectory when off);
    ``audit`` runs the static ``repro.analysis.audit`` passes — host-sync,
    baked-const, donation — on the trainer's hot loop before the timed run
    and raises :class:`~repro.analysis.AuditError` on any error finding.
    ``ef_rebase_threshold`` > 0 switches the EF gossip wire to the adaptive
    drift-proxy re-base (replaces the fixed ``ef_rebase_every`` clock).
    """
    fed, init_fn, apply_fn = make_task(dataset, num_nodes, seed)
    kwargs = {"p": p, "seed": seed} if graph == "erdos_renyi" else {"seed": seed}
    if graph in ("ring", "grid", "hypercube", "complete", "torus"):
        kwargs = {}
    base_lr = lr if lr is not None else 0.1
    if robust and lr_compensate:
        ell0 = np.log(10.0)  # untrained 10-class CE
        base_lr = base_lr * mu / float(np.exp(ell0 / mu))
    mixer = None
    put_state = None
    if lowering == "gossip":
        if local_updates != 1 or gradient_tracking or straggler_p or outage_p:
            raise ValueError("the gossip lowering here serves the topology/"
                             "compression axes; compose local updates and "
                             "faults on the dense lowering")
        params0 = init_fn(jax.random.PRNGKey(seed))
        node_params = jax.tree.map(
            lambda x: np.broadcast_to(np.asarray(x)[None],
                                      (num_nodes,) + np.asarray(x).shape),
            params0)
        make_mixer, put_state = _gossip_mixer(
            graph, kwargs, num_nodes, topology, drop_p, seed, compression,
            ef_rebase_every, ef_rebase_threshold)
        mixer = make_mixer(node_params)
    elif lowering == "hierarchical":
        if (local_updates != 1 or gradient_tracking or straggler_p
                or outage_p or compression is not None
                or topology != "static"):
            raise ValueError("the hierarchical lowering runs the static "
                             "psum-then-gossip stack; compose dynamics on "
                             "the dense lowering")
        params0 = init_fn(jax.random.PRNGKey(seed))
        node_params = jax.tree.map(
            lambda x: np.broadcast_to(np.asarray(x)[None],
                                      (num_nodes,) + np.asarray(x).shape),
            params0)
        make_mixer, put_state = _hierarchical_mixer(
            graph, kwargs, num_nodes, replicas, seed)
        mixer = make_mixer(node_params)
    spec = TrainerSpec(
        num_nodes=num_nodes,
        graph=graph,
        graph_kwargs=kwargs,
        mu=mu,
        robust=robust,
        lr=base_lr,
        grad_clip=grad_clip,
        compress=compression if compression is not None else "none",
        topology=topology if mixer is None else "static",
        drop_p=drop_p if mixer is None else 0.0,
        local_updates=local_updates,
        gradient_tracking=gradient_tracking,
        straggler_p=straggler_p,
        outage_p=outage_p,
        seed=seed,
        ef_rebase_threshold=ef_rebase_threshold if mixer is None else 0.0,
        sanitize=sanitize,
    )
    trainer = spec.build(make_classifier_loss(apply_fn), apply_fn,
                         mixer=mixer, obs=obs)
    state = trainer.init(init_fn(jax.random.PRNGKey(seed)))
    if put_state is not None:
        state = put_state(state)
    rng = np.random.default_rng(seed)
    x_nodes, y_nodes = fed.per_node_test_sets(n_per_node=200, seed=seed)
    history = []
    seg = min(eval_every, steps)
    if audit:
        # static-analysis gate on the hot loop (repro.analysis.audit):
        # host-sync hazards, baked scalar consts, donation failures.  Pure
        # trace/AOT probes — nothing executes, the param/rng streams are
        # untouched — and it runs BEFORE watch.track so any probe program
        # stays outside the watchdog's compile budget.
        from repro.analysis import AuditError, audit_train_step
        audit_rng = np.random.default_rng(seed)
        report = audit_train_step(
            trainer, state, tuple(map(jnp.asarray,
                                      fed.sample_batch(audit_rng, batch))))
        # donation is advisory here: on the forced host-platform CPU mesh
        # XLA aliases only part of the sharded scan carry (a backend
        # property, not a program bug — the dense single-device lowering
        # aliases fully), so only host-sync/baked-const/wire errors gate
        hard = [f for f in report.errors if f.code != "donation"]
        if hard:
            raise AuditError("\n".join(str(f) for f in hard))
        for f in report.findings:
            if f.code == "donation":
                print(f"audit advisory: {f}")
    # zero-recompile guard on the scan driver: one compiled program per
    # configuration; a ragged final segment legitimately compiles one more
    # scan length.  Raises RecompileError when a traced operand (topology,
    # rate, mask, round mode) leaks into program structure.
    watch = RecompileWatchdog(label=f"run_decentralized[{dataset}]")
    watch.track("run", trainer._run,
                allowed=1 if steps % seg == 0 else 2)
    # cumulative wire bytes: under an adaptive schedule comm_bytes moves
    # per round, so the bytes axis must integrate the traced metric rather
    # than multiply a per-round constant by the step count.  Accumulate as
    # a device array — float() every segment would force a host sync inside
    # the timed loop and pollute us_per_step.
    cum_bytes_dev = jnp.float32(0.0)
    comm_bytes_round = None

    def eval_segment(last_step, seg_state, ms):
        stats = trainer.eval_local_distributions(seg_state, x_nodes, y_nodes)
        stats["step"] = last_step
        stats["cum_bytes"] = float(cum_bytes_dev)
        if compression is not None:
            stats["ef_residual_norm"] = float(ms["ef_residual_norm"][-1])
        if "disagreement" in ms:
            # Lemma-3 consensus error — the metric the wire codec moves
            # (the memoryless ablation stalls here, EF keeps contracting)
            stats["disagreement"] = float(ms["disagreement"][-1])
        history.append(stats)

    # first segment warms up the compiled scan program (excluded from timing,
    # like the old per-step warmup); subsequent segments run the same program
    stacked = stack_batches(fed, rng, batch, seg)
    t_warm = time.perf_counter()
    state, ms = trainer.run(state, stacked)
    jax.block_until_ready(state.params)
    warm_wall = time.perf_counter() - t_warm
    # peak per-round wire of the first segment: step 0 alone would read 0
    # under local_updates > 1 (a local round) and a random draw under
    # dropout; the max is the full-topology consensus-round figure and
    # matches the old step-0 read exactly for static synchronous runs
    comm_bytes_round = float(jnp.max(ms["comm_bytes"]))
    cum_bytes_dev = cum_bytes_dev + jnp.sum(ms["comm_bytes"])
    eval_segment(seg - 1, state, ms)
    done = seg
    wall = 0.0
    timed_steps = 0
    while done < steps:
        n = min(seg, steps - done)
        # host-side sampling stays outside the timed region, and the timer
        # only stops once the device results land (async dispatch would
        # otherwise hand the compute bill to the untimed eval below)
        stacked = stack_batches(fed, rng, batch, n)
        t0 = time.perf_counter()
        state, ms = trainer.run(state, stacked)
        jax.block_until_ready(state.params)
        dt = time.perf_counter() - t0
        if n == seg:
            # only full segments reuse the warmed program; a ragged final
            # segment compiles a second scan length and would pollute timing
            wall += dt
            timed_steps += n
        cum_bytes_dev = cum_bytes_dev + jnp.sum(ms["comm_bytes"])
        done += n
        eval_segment(done - 1, state, ms)
    if timed_steps == 0:
        # no full post-warmup segment ran (steps < 2*seg): fall back to the
        # warmup segment — seg steps of wall, compile included
        wall, timed_steps = warm_wall, seg
    cum_bytes = float(cum_bytes_dev)
    programs = watch.check()["run"]
    final = history[-1]
    return {
        "dataset": dataset,
        "algo": "DR-DSGD" if robust else "DSGD",
        "mu": mu if robust else float("inf"),
        "graph": graph,
        "p": p,
        "num_nodes": num_nodes,
        "rho": trainer.rho,
        "steps": steps,
        "compress": compression.kind if compression is not None else "none",
        "topology": topology,
        "drop_p": drop_p,
        "local_updates": local_updates,
        "lowering": lowering,
        "ef_rebase_every": ef_rebase_every,
        "ef_rebase_threshold": ef_rebase_threshold,
        "sanitize": sanitize,
        # compiled scan programs the run used (1 = zero recompiles across
        # rounds; +1 tolerated for a ragged final segment) — already checked
        # by the watchdog above, reported for the benchmark rows
        "run_programs": programs,
        "params_digest": params_digest(state.params),
        "comm_bytes_per_round": comm_bytes_round,
        "comm_bytes_total": cum_bytes,
        "us_per_step": wall / timed_steps * 1e6,
        "disagreement_final": final.get("disagreement"),
        "acc_avg": final["acc_avg"],
        "acc_worst_dist": final["acc_worst_dist"],
        "acc_node_std": final["acc_node_std"],
        "history": history,
    }


def rounds_to_target(history, target: float) -> int | None:
    """Communication rounds needed to reach a worst-distribution accuracy."""
    for h in history:
        if h["acc_worst_dist"] >= target:
            return h["step"]
    return None


def bytes_to_target(history, target: float) -> float | None:
    """Cumulative wire bytes needed to reach a worst-distribution accuracy."""
    for h in history:
        if h["acc_worst_dist"] >= target:
            return h["cum_bytes"]
    return None


def fmt_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"
