"""DR-DSGD / DSGD decentralized train-step builders (paper Alg. 1 & 2).

The train step operates on a :class:`DecentralizedState` whose params pytree is
*node-stacked*: every leaf has leading axis K.  One step is:

  1. per-node minibatch gradient  g_i  and minibatch loss  ℓ̄_i   (vmap over K)
  2. robust scale   s_i = exp(ℓ̄_i/μ)/μ     (DR-DSGD; s_i = 1 for DSGD)
  3. local update   θ_i⁺ = opt(θ_i, s_i·g_i)
  4. consensus      θ, comm ← mix(θ⁺, comm, round=step)

Step 4 is the uniform Mixer protocol (``repro.comm.protocol``): every mixer
— identity, dense, gossip, hierarchical, compressed, repeated — threads one
``CommState`` through ``DecentralizedState.comm``, so there is exactly one
consensus code path regardless of the wire codec.

Distribution: under pjit the node axis is sharded over the mesh's data axes,
so step 1-3 are embarrassingly parallel and step 4 is the only communication
(this is the paper's communication pattern, made explicit for XLA).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import CompressionConfig
from repro.comm.protocol import CommState, Mixer, trivial_comm_state
from repro.core.robust import RobustConfig, mixture_weights, robust_objective, robust_scale
from repro.obs.hist import TRAIN_HISTOGRAMS, HistSpec, hist_counts
from repro.obs.profiler import scope
from repro.optim.optimizers import Optimizer
from repro.utils.tree import tree_node_disagreement

LossFn = Callable[[Any, Any], jax.Array]  # (params, batch) -> scalar loss


class DecentralizedState(NamedTuple):
    params: Any          # node-stacked pytree, leading axis K
    opt_state: Any
    step: jax.Array      # scalar int32
    comm: Any = ()       # the mixer's CommState (trivial for uncompressed)

    @property
    def ef_state(self):
        """Pre-v2 alias for :attr:`comm` (the CommState of the mixer)."""
        return self.comm


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    robust: RobustConfig
    grad_clip: float | None = None        # per-node global-norm clip (pre-scale)
    metrics_disagreement: bool = True     # Lemma-3 discrepancy metric (extra comm)
    mix_every: int = 1                    # consensus period: 1 = DSGD/DR-DSGD;
                                          # >1 + complete graph = FedAvg-style
                                          # local SGD with periodic averaging
    compression: CompressionConfig | None = None
                                          # wire codec the mixer was built
                                          # with (repro.comm); recorded here
                                          # so the step can sanity-check the
                                          # mixer
    histograms: tuple[HistSpec, ...] = TRAIN_HISTOGRAMS
                                          # in-jit streaming histograms
                                          # (repro.obs.hist) joining the
                                          # tap's decimated vector payload;
                                          # only computed when obs is given


def init_state(node_params, optimizer: Optimizer,
               mixer: Mixer | None = None) -> DecentralizedState:
    """Build state from node-stacked params (see utils.tree.tree_stack_nodes).

    Pass the mixer so its ``CommState`` is allocated into ``comm``; without
    one the trivial state is used (correct for any uncompressed mixer).
    """
    comm = mixer.init_state(node_params) if mixer is not None \
        else trivial_comm_state()
    return DecentralizedState(
        params=node_params,
        opt_state=optimizer.init(node_params),
        step=jnp.zeros((), jnp.int32),
        comm=comm,
    )


def replicate_params(params, k: int):
    """Broadcast a single param pytree to K identical node replicas.

    The theory (Lemma 3) assumes all local models start at the same point.
    """
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (k,) + x.shape), params
    )


def build_train_step(
    loss_fn: LossFn,
    optimizer: Optimizer,
    mixer: Mixer,
    cfg: TrainStepConfig,
    loss_has_aux: bool = False,
    obs=None,
    sanitize: bool = False,
):
    """Returns train_step(state, batch) -> (state, metrics).

    ``batch`` is a pytree whose leaves carry a leading node axis K, matching
    the params' node axis.  ``loss_fn(params_i, batch_i)`` must return a
    scalar (or (scalar, aux-dict) with ``loss_has_aux``).

    ``obs`` is an optional :class:`repro.obs.MetricsSink`: when given, every
    step packs its record — the scalar metrics plus the per-node vectors
    (``loss_nodes``, ``dr_weights``) and the in-jit histogram counts
    (``cfg.histograms``, :mod:`repro.obs.hist`) — into flat f32 payload
    leaves (``obs.tap_pack``) merged into the returned metrics dict, where
    ``lax.scan`` stacks them for free: ZERO host callbacks in the compiled
    program.  ``trainer.run`` drains the payload per segment
    (``obs.tap_drain``), decimating the vector fields to every
    ``obs.vector_every``-th record.  The tap only reads values the step
    computes anyway and the payload leaves are popped before metrics reach
    the caller, so the visible metrics tree, the scan carry's donation, and
    the trajectory stay bit-exact vs ``obs=None``.

    ``sanitize`` stages the runtime invariant checks of
    ``repro.analysis.sanitize`` (doubly-stochastic W, CHOCO cache drift,
    finite mixed params, in-container codec rate) after the consensus.
    They are ``checkify.check`` calls: the returned step must then run
    under a ``checkify.checkify`` transform (the trainer wraps it), and
    the computed values are untouched — the trajectory stays bit-exact vs
    ``sanitize=False``.
    """

    grad_fn = jax.value_and_grad(loss_fn, has_aux=loss_has_aux)
    step_checks = None
    if sanitize:
        from repro.analysis.sanitize import step_checks
    if cfg.compression is not None and cfg.compression.enabled \
            and mixer.compression is None:
        raise ValueError(
            "TrainStepConfig.compression is set but the mixer is "
            "uncompressed — build it with the same CompressionConfig "
            "(see repro.core.consensus factories)")
    if cfg.mix_every > 1 and getattr(mixer, "period", 1) > 1:
        raise ValueError(
            "mix_every > 1 with a LocalUpdateMixer (period > 1) runs two "
            "consensus clocks against each other — express the local-update "
            "period in ONE place (the mixer's period is the dynamics-aware "
            "spelling: it keeps CommState.rounds ticking every step)")
    # scheduled codecs move the rate every round, so the static estimate is
    # wrong for them: report the mixer's traced per-round wire_bits instead
    # (and skip computing the dead static estimate entirely)
    traced_wire = mixer.traced_wire
    # straggler-skips-compute: replay the mixer's node-up vector to zero the
    # robust gradient scale of down nodes (FaultConfig.straggler_skips_compute;
    # the fault process is a pure function of CommState.rounds, so the mask
    # matches the consensus round's link failures exactly).  Unwrap stacking
    # wrappers (LocalUpdateMixer/RepeatMixer) to find the faulted mixer.
    _m, step_faults = mixer, None
    while _m is not None and step_faults is None:
        step_faults = getattr(getattr(_m, "topo", None), "faults", None)
        _m = getattr(_m, "inner", None)
    if not (step_faults is not None and step_faults.enabled
            and step_faults.straggler_skips_compute
            and (step_faults.straggler_p > 0 or step_faults.outage_p > 0)):
        step_faults = None

    def per_node(params_i, batch_i):
        if loss_has_aux:
            (loss, aux), grads = grad_fn(params_i, batch_i)
        else:
            loss, grads = grad_fn(params_i, batch_i)
            aux = {}
        if cfg.grad_clip is not None:
            from repro.optim.optimizers import clip_by_global_norm

            grads, _ = clip_by_global_norm(grads, cfg.grad_clip)
        return loss, grads, aux

    def train_step(state: DecentralizedState, batch):
        if not isinstance(state.comm, CommState):
            raise ValueError(
                "DecentralizedState.comm must be the mixer's CommState — "
                "build the state with init_state(params, optimizer, "
                "mixer=mixer) (protocol v2: every mixer, compressed or "
                "not, carries one)")
        with scope("obs:grad"):
            losses, grads, aux = jax.vmap(per_node)(state.params, batch)
        # --- the paper's technique: exponential per-node gradient reweighting
        with scope("obs:dr_weighting"):
            scale = robust_scale(losses, cfg.robust)  # (K,)
            lam = mixture_weights(losses, cfg.robust)  # (K,) adversarial λ*
            if step_faults is not None:
                from repro.dynamics.faults import fault_keep_matrix

                # pre-increment clock: the same round index the mixer's
                # fault replay will consume this step
                _, up = fault_keep_matrix(
                    step_faults, state.comm.rounds, losses.shape[0])
                scale = scale * up
            scaled_grads = jax.tree.map(
                lambda g: g * scale.reshape((-1,) + (1,) * (g.ndim - 1)).astype(g.dtype),
                grads,
            )
        # --- local optimizer step (plain SGD in the paper)
        with scope("obs:local_update"):
            updated, opt_state = optimizer.update(
                scaled_grads, state.opt_state, state.params, state.step
            )
        # --- consensus: the only cross-node communication of the algorithm.
        # One protocol for every mixer; mix_every > 1 skips communication on
        # off-steps (local SGD / periodic averaging, the FedAvg-style PS
        # baseline of paper §1-2) and passes CommState through untouched.
        is_mix_step = state.step % cfg.mix_every == cfg.mix_every - 1
        with scope("obs:consensus"):
            if cfg.mix_every == 1:
                mixed, comm = mixer(updated, state.comm, round=state.step)
            else:
                mixed, comm = jax.lax.cond(
                    is_mix_step,
                    lambda theta, cs: mixer(theta, cs, round=state.step),
                    lambda theta, cs: (theta, cs),
                    updated, state.comm)
        if step_checks is not None:
            with scope("obs:sanitize"):
                step_checks(mixer, state.comm, mixed, comm)
        # the step's reported numbers: loss reductions, the consensus
        # discrepancy, wire bytes
        with scope("obs:metrics"):
            # estimated wire bytes this step (static estimate, gated on mixing;
            # traced wire_bits/8 when a schedule makes the rate dynamic)
            if traced_wire:
                comm_bytes = jnp.where(is_mix_step, comm.wire_bits / 8.0, 0.0)
            else:
                # bytes_per_round is shape-only host math on static mixers
                # (traced_wire is False here): no tracer reaches the float()
                round_bytes = float(mixer.bytes_per_round(state.params))  # repro: noqa[RPR002]
                if cfg.mix_every == 1:
                    comm_bytes = jnp.float32(round_bytes)
                else:
                    comm_bytes = jnp.where(is_mix_step, round_bytes, 0.0)
            cm = comm.metrics
            metrics = {
                "comm_bytes": comm_bytes,
                "loss_mean": jnp.mean(losses),
                "loss_worst": jnp.max(losses),
                "loss_std": jnp.std(losses),
                "robust_objective": robust_objective(losses, cfg.robust),
                "scale_mean": jnp.mean(scale),
                "scale_max": jnp.max(scale),
                "lambda_max": jnp.max(lam),
                # wire_bits is "bits injected by the last round" — gate on the
                # mix predicate so off-steps (mix_every > 1) report 0, not the
                # stale value the lax.cond pass-through branch carries
                "wire_bits": jnp.where(is_mix_step, cm.wire_bits, 0.0),
                "ef_residual_norm": cm.res_norm,
            }
            if cfg.metrics_disagreement:
                metrics["disagreement"] = tree_node_disagreement(mixed)
            for k, v in aux.items():
                metrics[f"aux_{k}"] = jnp.mean(v)
        if obs is not None:
            # pack the step's record for the host sink.  The per-node
            # vectors (the paper's trajectory axes) and the in-jit histogram
            # counts ride only on the tap payload — decimated to every
            # obs.vector_every-th step at drain — not in the named metrics,
            # so the visible metrics tree is identical with the sink on or
            # off.  The payload leaves ride the scan's stacked outputs (no
            # host callback); trainer.run drains them when a segment returns.
            with scope("obs:tap"):
                rec = dict(metrics)
                # EF wire bookkeeping for host-side event derivation
                # (re-base firings / drift), when the mixer carries it
                for name in ("ef_rounds", "ef_drift"):
                    v = getattr(comm, name, ())
                    if hasattr(v, "dtype"):
                        rec[name] = v
                vectors = {
                    "loss_nodes": losses.astype(jnp.float32),
                    "dr_weights": lam,
                }
                hist_sources = {
                    "loss_nodes": losses,
                    "dr_weights": lam,
                    "ef_res": cm.res_norm,
                }
                for spec in cfg.histograms:
                    src = hist_sources.get(spec.source)
                    if src is not None:
                        vectors[spec.field] = hist_counts(src, spec)
                metrics = dict(metrics)
                metrics.update(obs.tap_pack(state.step, rec,
                                            vectors=vectors))
        return (
            DecentralizedState(mixed, opt_state, state.step + 1, comm),
            metrics,
        )

    return train_step


def build_eval_step(predict_fn: Callable[[Any, Any], jax.Array]):
    """Returns eval_step(node_params, x, y) -> (K,) per-node accuracies.

    Every node evaluates the *same* test inputs — matching the paper's
    protocol of reporting each device's test accuracy on the global test set
    (worst distribution accuracy = min over per-class/per-node accuracies).
    """

    def eval_step(node_params, x, y):
        def one(params_i):
            logits = predict_fn(params_i, x)
            return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

        return jax.vmap(one)(node_params)

    return eval_step
