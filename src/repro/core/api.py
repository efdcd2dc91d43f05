"""High-level DecentralizedTrainer: graph + mixer + step, one object.

This is the public API used by the examples and benchmarks:

    trainer = DecentralizedTrainer(
        loss_fn, predict_fn, num_nodes=10,
        graph="erdos_renyi", graph_kwargs={"p": 0.3},
        robust=RobustConfig(mu=6.0), lr=0.05)
    state = trainer.init(params_single)
    state, metrics = trainer.step(state, batch)      # one jitted step
    state, ms = trainer.run(state, batches)          # scan-compiled multi-step
    accs = trainer.eval_per_node(state, x_test, y_test)

``run`` is the hot-loop driver: it folds N train steps into ONE compiled
``jax.lax.scan`` program with the carried state donated, so the per-step
Python dispatch overhead of the ``step`` loop disappears (see EXPERIMENTS.md
§Run-driver for measured steps/s).  ``batches`` is the step-loop batch pytree
stacked along a leading time axis; metrics come back stacked the same way.
Declarative construction (CLI flags, benchmarks, examples) goes through
:class:`repro.core.spec.TrainerSpec` → ``spec.build(loss_fn, ...)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import CompressionConfig
from repro.comm.protocol import Mixer
from repro.core.consensus import make_dense_mixer, make_identity_mixer
from repro.core.drdsgd import (
    DecentralizedState,
    TrainStepConfig,
    build_eval_step,
    build_train_step,
    init_state,
    replicate_params,
)
from repro.core.robust import RobustConfig
from repro.dynamics import build_dynamic_mixer
from repro.graphs import build_graph, metropolis_weights, spectral_norm
from repro.obs.profiler import PhaseTimer
from repro.optim import Optimizer, sgd


def run_segments(trainer: "DecentralizedTrainer", state, sample_batch,
                 steps: int, seg: int, on_segment=None, *, obs=None):
    """Drive ``trainer.run`` in host-sampled logging segments.

    For data pipelines that sample batches host-side per step
    (``sample_batch(step) -> batch pytree`` of numpy/array leaves): batches
    are stacked ``seg`` at a time, so device memory holds at most one
    segment while the scan driver amortizes dispatch across it.
    ``on_segment(last_step, state, seg_metrics)`` runs between compiled
    segments (the epoch-level host hook; same retention caveat as
    ``run`` — eval the state inside the hook, don't keep it).

    ``obs`` (a :class:`repro.obs.MetricsSink`) adds the phase-timer rollup:
    every chunk emits one ``perf`` record (steps/s, wire bytes/s, wall-clock
    per ``sample``/``run``/``hook`` phase) into the telemetry stream, and
    the ``run`` phase blocks on the segment's results so the timings are
    wall-clock honest (one host sync per *segment* — the per-step taps stay
    async).
    """
    timer = PhaseTimer() if obs is not None else None
    done = 0
    while done < steps:
        n = min(seg, steps - done)
        if timer is None:
            stacked = jax.tree.map(
                lambda *xs: jnp.asarray(np.stack(xs)),
                *[sample_batch(done + i) for i in range(n)])
            state, ms = trainer.run(state, stacked)
            done += n
            if on_segment is not None:
                on_segment(done - 1, state, ms)
            continue
        with timer.phase("sample"):
            stacked = jax.tree.map(
                lambda *xs: jnp.asarray(np.stack(xs)),
                *[sample_batch(done + i) for i in range(n)])
        with timer.phase("run"):
            state, ms = trainer.run(state, stacked)
            jax.block_until_ready(ms)
        done += n
        if on_segment is not None:
            with timer.phase("hook"):
                on_segment(done - 1, state, ms)
        wire = (float(jnp.sum(ms["comm_bytes"]))
                if "comm_bytes" in ms else None)
        obs.log("perf", done - 1,
                **timer.rollup(steps=n, wire_bytes=wire))
        timer.reset()
    return state


@dataclasses.dataclass
class DecentralizedTrainer:
    """Decentralized (DR-)DSGD trainer over a communication graph."""

    loss_fn: Callable[[Any, Any], jax.Array]
    predict_fn: Callable[[Any, Any], jax.Array] | None = None
    num_nodes: int = 10
    graph: str = "erdos_renyi"
    graph_kwargs: dict = dataclasses.field(default_factory=dict)
    robust: RobustConfig = dataclasses.field(default_factory=RobustConfig)
    optimizer: Optimizer | None = None
    lr: float = 0.05
    grad_clip: float | None = None
    mixer: Mixer | None = None            # override (e.g. gossip mixer on a mesh)
    mixing: str = "metropolis"            # or "max_degree", "none"
    compression: CompressionConfig | None = None
                                          # wire codec for the consensus step
                                          # (repro.comm); None = full precision
    dynamics: Any = None                  # repro.dynamics.DynamicsConfig:
                                          # time-varying topology / faults /
                                          # local updates; None = static
                                          # synchronous consensus
    mix_every: int = 1                    # consensus period (local SGD when >1)
    metrics_disagreement: bool = True     # Lemma-3 discrepancy metric; costs an
                                          # extra cross-node reduction per step
    obs: Any = None                       # repro.obs.MetricsSink: stream the
                                          # per-step record (metrics + per-node
                                          # losses/DR weights) to the host via
                                          # an in-graph tap; None = no telemetry
    loss_has_aux: bool = False
    jit: bool = True
    sanitize: bool = False                # checkify-wrap the step with the
                                          # runtime invariant checks of
                                          # repro.analysis.sanitize; a failed
                                          # check raises on the host at the
                                          # next err.throw() (per step/run),
                                          # params stay bit-exact when off

    def __post_init__(self):
        g = build_graph(self.graph, self.num_nodes, **self.graph_kwargs)
        if not g.is_connected():
            raise ValueError("communication graph must be connected (Assumption 5)")
        self.graph_obj = g
        if self.mixing == "none":
            self.w = np.eye(self.num_nodes)
        elif self.mixing == "metropolis":
            self.w = metropolis_weights(g)
        elif self.mixing == "max_degree":
            from repro.graphs import max_degree_weights

            self.w = max_degree_weights(g)
        else:
            raise ValueError(f"unknown mixing {self.mixing!r}")
        self.rho = spectral_norm(self.w)
        dyn = self.dynamics if (self.dynamics is not None
                                and self.dynamics.enabled) else None
        if self.mixer is None:
            if dyn is not None and self.mixing != "none":
                # dynamic topology / faults / local updates: the
                # dense-lowering stack of repro.dynamics
                self.mixer = build_dynamic_mixer(
                    dyn, self.w, compression=self.compression)
            else:
                self.mixer = (
                    make_identity_mixer() if self.mixing == "none"
                    else make_dense_mixer(self.w, compression=self.compression)
                )
        else:
            if dyn is not None:
                raise ValueError(
                    "both a pre-built mixer and a DynamicsConfig were "
                    "provided — wrap the mixer yourself (repro.dynamics."
                    "LocalUpdateMixer / make_gossip_mixer(schedule=...)) "
                    "or drop one")
            if self.compression is not None and self.compression.enabled \
                    and self.mixer.compression is None:
                raise ValueError(
                    "compression is set but the provided mixer is "
                    "uncompressed; build the mixer with the same "
                    "CompressionConfig")
        if self.optimizer is None:
            self.optimizer = sgd(self.lr)
        step_cfg = TrainStepConfig(
            robust=self.robust, grad_clip=self.grad_clip,
            metrics_disagreement=self.metrics_disagreement,
            compression=self.compression, mix_every=self.mix_every)
        self._train_step_fn = build_train_step(
            self.loss_fn, self.optimizer, self.mixer, step_cfg,
            loss_has_aux=self.loss_has_aux, obs=self.obs,
            sanitize=self.sanitize,
        )
        if self.sanitize:
            # the step stages checkify.check calls: transform once, jit the
            # transformed fn, and surface failures host-side via err.throw()
            from jax.experimental import checkify

            checked_step = checkify.checkify(
                self._train_step_fn, errors=checkify.user_checks)
            jitted_step = (jax.jit(checked_step) if self.jit
                           else checked_step)

            def step_and_throw(state, batch):
                err, out = jitted_step(state, batch)
                err.throw()
                return out

            if self.jit:
                # keep the wrapper trackable by RecompileWatchdog
                step_and_throw._cache_size = jitted_step._cache_size
            self._train_step = step_and_throw
        else:
            self._train_step = (jax.jit(self._train_step_fn) if self.jit
                                else self._train_step_fn)

        if self.sanitize:
            from jax.experimental import checkify

            checked_body = checkify.checkify(
                self._train_step_fn, errors=checkify.user_checks)

            def scan_run(state, batches):
                # discharge checkify PER STEP inside the scan body: the
                # error reaching the mixer's shard_map is then always the
                # empty one (checkify's shard_map rule reshapes any live
                # error to per-device shape, which breaks the scan carry),
                # and the per-step errors ride out as a stacked scan output
                # for one batched throw() on the host
                def body(st, batch):
                    err, (st2, m) = checked_body(
                        jax.lax.optimization_barrier(st), batch)
                    return st2, (err, m)

                state, (errs, ms) = jax.lax.scan(body, state, batches)
                return state, (errs, ms)
        else:

            def scan_run(state, batches):
                # the barrier keeps XLA from fusing across a step boundary:
                # a one-step segment (whose loop XLA removes) then computes
                # bit-for-bit what the same step does inside a longer scan,
                # so a run's result does not depend on how it is segmented
                def body(st, batch):
                    return self._train_step_fn(
                        jax.lax.optimization_barrier(st), batch)

                return jax.lax.scan(body, state, batches)

        # the jittable scan driver, kept for the static auditor
        # (repro.analysis.audit probes donation on it even when the
        # err.throw() wrapping makes self._run a host-throwing closure)
        self._scan_run_fn = scan_run

        def eager_run(state, batches):
            # jit=False debugging path: plain Python loop so prints and
            # breakpoints inside loss_fn still fire (scan would trace them)
            t = jax.tree.leaves(batches)[0].shape[0]
            out = []
            for i in range(t):
                state, m = self._train_step(
                    state, jax.tree.map(lambda x: x[i], batches))
                out.append(m)
            return state, jax.tree.map(lambda *xs: jnp.stack(xs), *out)

        # the multi-step driver: one compiled program for N steps, with the
        # carried DecentralizedState donated (params/opt/comm buffers are
        # reused in place on backends that support donation)
        if self.sanitize and self.jit:
            checked_run = jax.jit(scan_run, donate_argnums=(0,))

            def run_and_throw(state, batches):
                state, (errs, ms) = checked_run(state, batches)
                errs.throw()  # batched over steps: reports every violation
                return state, ms

            # keep the wrapper trackable by RecompileWatchdog
            run_and_throw._cache_size = checked_run._cache_size
            self._run = run_and_throw
        elif self.jit:
            self._run = jax.jit(scan_run, donate_argnums=(0,))
        else:
            self._run = eager_run
        if self.predict_fn is not None:
            self._eval_step = build_eval_step(self.predict_fn)
            if self.jit:
                self._eval_step = jax.jit(self._eval_step)

    # -- public API ---------------------------------------------------------

    def init(self, params_single) -> DecentralizedState:
        """All nodes start at the same point (Lemma 3 precondition)."""
        node_params = replicate_params(params_single, self.num_nodes)
        return init_state(node_params, self.optimizer, mixer=self.mixer)

    def init_stacked(self, node_params) -> DecentralizedState:
        return init_state(node_params, self.optimizer, mixer=self.mixer)

    def step(self, state: DecentralizedState, batch):
        state, metrics = self._train_step(state, batch)
        return state, self._drain_tap(metrics)

    def _drain_tap(self, metrics):
        """Pop the batched-tap payload a segment returned and deliver its
        records to the sink — keeps the metrics tree callers see identical
        with the sink on or off (see ``MetricsSink.tap_drain``)."""
        if self.obs is None:
            return metrics
        return self.obs.tap_drain(metrics)

    def run(self, state: DecentralizedState, batches, *, steps: int | None = None,
            epoch_steps: int | None = None, on_epoch=None):
        """Run many train steps as one ``lax.scan`` program.

        Args:
          state: carried :class:`DecentralizedState` — DONATED to the
            compiled program; do not reuse the passed-in buffers afterwards
            (on CPU donation is a no-op, but portable callers should treat
            the argument as consumed).
          batches: the per-step batch pytree stacked along a new leading time
            axis, i.e. every leaf is (T, K, ...) where ``step`` takes
            (K, ...).  Build it host-side with ``np.stack``.
          steps: optional step count; defaults to the leading dim T of the
            stacked batches, and slices the batches when smaller.
          epoch_steps / on_epoch: host-callback hook for eval/logging —
            the scan is chopped into epochs of ``epoch_steps`` steps and
            ``on_epoch(epoch_index, state, epoch_metrics)`` runs as plain
            Python between the compiled segments (``epoch_metrics`` is the
            metrics dict of that segment, each leaf (epoch_steps,)).  Equal
            epochs reuse one compiled program; a ragged final epoch costs
            one extra compile.  The per-epoch ``state`` handed to the hook
            is donated into the NEXT segment: read/eval it inside the hook,
            but do not retain it (on donation backends its buffers are
            invalidated as soon as the next segment launches; copy leaves
            you need to keep).

        Returns:
          (final_state, metrics) with every metric stacked to (steps,).
        """
        leaves = jax.tree.leaves(batches)
        if not leaves:
            raise ValueError("run() needs a non-empty batches pytree")
        total = leaves[0].shape[0]
        if steps is None:
            steps = total
        elif steps > total:
            raise ValueError(f"steps={steps} > stacked batches T={total}")
        elif steps < total:
            batches = jax.tree.map(lambda x: x[:steps], batches)
        if on_epoch is None or epoch_steps is None or epoch_steps >= steps:
            state, metrics = self._run(state, batches)
            metrics = self._drain_tap(metrics)
            if on_epoch is not None:
                on_epoch(0, state, metrics)
            return state, metrics
        chunks = []
        for e, start in enumerate(range(0, steps, epoch_steps)):
            seg = jax.tree.map(
                lambda x: x[start:start + epoch_steps], batches)
            state, ms = self._run(state, seg)
            ms = self._drain_tap(ms)
            on_epoch(e, state, ms)
            chunks.append(ms)
        metrics = jax.tree.map(lambda *xs: jnp.concatenate(xs), *chunks)
        return state, metrics

    def eval_per_node(self, state: DecentralizedState, x, y) -> jax.Array:
        if self.predict_fn is None:
            raise ValueError("predict_fn not provided")
        return self._eval_step(state.params, jnp.asarray(x), jnp.asarray(y))

    def eval_local_distributions(self, state: DecentralizedState, x_nodes,
                                 y_nodes) -> dict:
        """Paper §6.2 protocol: device i's model on device i's distribution.

        x_nodes: (K, n, ...), y_nodes: (K, n). Worst distribution test
        accuracy = min_i acc(θ_i, D_i^test); fairness = STDEV across devices.
        """
        if self.predict_fn is None:
            raise ValueError("predict_fn not provided")

        def one(params_i, x_i, y_i):
            logits = self.predict_fn(params_i, x_i)
            return jnp.mean((jnp.argmax(logits, -1) == y_i).astype(jnp.float32))

        accs = np.asarray(jax.vmap(one)(
            state.params, jnp.asarray(x_nodes), jnp.asarray(y_nodes)))
        return {
            "acc_avg": float(accs.mean()),
            "acc_worst_dist": float(accs.min()),
            "acc_node_std": float(accs.std()),
            "acc_node_min": float(accs.min()),
            "acc_nodes": [float(a) for a in accs],
        }

    def eval_worst_distribution(self, state: DecentralizedState, per_class_sets
                                ) -> dict:
        """Paper's metrics: avg / worst-distribution accuracy + STDEV.

        ``per_class_sets`` is a list of (x, y) test subsets (one per class or
        per target distribution). Worst-distribution accuracy = min over
        subsets of the consensus-model accuracy; per-node stats use each
        node's own model on the full test set (paper Figs. 2-4).
        """
        kept = [(x, y) for x, y in per_class_sets if len(y)]
        if not kept:
            raise ValueError(
                "eval_worst_distribution needs at least one non-empty test "
                "subset; all per_class_sets entries are empty")
        accs = [float(jnp.mean(self.eval_per_node(state, x, y)))
                for x, y in kept]
        x_all = np.concatenate([x for x, _ in kept])
        y_all = np.concatenate([y for _, y in kept])
        node_accs = np.asarray(self.eval_per_node(state, x_all, y_all))
        return {
            "acc_avg": float(node_accs.mean()),
            "acc_worst_dist": float(min(accs)),
            "acc_node_std": float(node_accs.std()),
            "acc_node_min": float(node_accs.min()),
            "acc_nodes": [float(a) for a in node_accs],
        }
