"""Decentralized (DR-)DSGD training driver.

Runs the paper's algorithm end-to-end on any of the assigned architectures
(synthetic token streams, per-node distribution shift) or the paper's own
MLP/CNN image models.  On the CPU use the ``--smoke`` configs; on a TPU the
same entry point trains the published widths, with the K nodes stacked on
one device (K=2 of qwen2-0.5b fits one 16 GB v5e at seq 512).  One node per
chip over a ``("node",)`` mesh is ``chip_smoke.py --four-chips``.

Trainer construction is declarative (``repro.core.TrainerSpec``: the same
flags drive the benchmarks and examples) and the hot loop runs through
``DecentralizedTrainer.run`` — one compiled ``lax.scan`` program per logging
segment with the carried state donated, instead of a per-step Python
dispatch loop.

Telemetry (``repro.obs``): every run streams through a
:class:`~repro.obs.MetricsSink` — the in-graph tap payload delivers
one ``train`` record per optimizer step (scalar metrics + per-node losses
and DR weights), the eval hook writes the paper's fairness metrics as
``eval`` records, and ``run_segments`` rolls up wall-clock phase timings as
``perf`` records.  The console lines below are *formatters over those same
records*; ``--log-dir`` additionally persists them as schema-versioned
JSONL (``python -m repro.obs.schema`` validates; ``python -m repro.obs
report <log-dir>`` renders the fairness/comm summary and derives the
per-round fault / EF re-base / rate-switch trace events), and ``--profile``
wraps the run in ``jax.profiler.trace`` (phases carry ``obs:...`` scopes).
Per-node vectors and in-jit histogram counts ride the tap decimated
(``--tap-vectors-every``); scalars land every step.

Dynamic graphs (``repro.dynamics``): ``--topology dropout --drop-p 0.3``
trains over per-round Bernoulli link failures (renormalized on device, one
compiled program for the whole run); ``--local-updates H`` runs H local
steps per consensus round, ``--gradient-tracking`` adds the drift
correction, and ``--straggler-p/--outage-p`` inject node faults.

Consensus wire compression (``repro.comm``): ``--compress`` selects the
codec (bf16 cast, int8/int4 stochastic-rounding quantization, topk/randk
sparsification with ``--compress-ratio``), all with error-feedback
innovation gossip so convergence tracks the uncompressed mixer while the
per-round ``comm_bytes`` metric drops 2-50x.

Sanitizer (``repro.analysis``): ``--sanitize`` checkify-wraps the compiled
step with runtime invariant checks — doubly-stochastic W each round, CHOCO
error-feedback cache drift, finite post-dequant parameters, in-container
codec rate.  A violation raises host-side at the next segment boundary; the
trajectory is bit-exact with the flag off (see EXPERIMENTS.md
§Static-analysis for the measured overhead).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --steps 20 --nodes 4 --batch-per-node 2 --seq-len 64
  PYTHONPATH=src python -m repro.launch.train --paper fmnist --steps 150
  PYTHONPATH=src python -m repro.launch.train --paper fmnist --steps 150 \
      --log-dir runs/fmnist --profile
  PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --smoke \
      --steps 20 --nodes 4 --compress topk --compress-ratio 0.05
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import save_train_state
from repro.configs import get_arch, fmnist_default, cifar_default
from repro.core import TrainerSpec, add_obs_cli_args, run_segments
from repro.data import (
    make_cifar_like,
    make_fmnist_like,
    make_node_token_streams,
    pathological_noniid_partition,
)
from repro.models import TransformerLM, mlp_init, mlp_apply, cnn_init, cnn_apply
from repro.models.paper_nets import make_classifier_loss
from repro.obs import (
    MetricsSink,
    format_eval,
    format_meta,
    format_train,
    profile,
)
from repro.utils.compile_cache import enable_compile_cache


def _dynamics_meta(spec: TrainerSpec) -> dict:
    """Fault/EF config fields of the meta record — what
    ``python -m repro.obs report`` needs to replay the run's fault events
    host-side (repro.obs.trace) without any device logging."""
    return dict(
        seed=spec.seed, drop_p=spec.drop_p, straggler_p=spec.straggler_p,
        outage_p=spec.outage_p, outage_len=spec.outage_len,
        ef_rebase_every=spec.ef_rebase_every,
        ef_rebase_threshold=spec.ef_rebase_threshold)


def train_lm(args, sink: MetricsSink):
    args.steps = args.steps or 50
    args.batch_per_node = args.batch_per_node or 2
    cfg = get_arch(args.arch, smoke=args.smoke)
    model = TransformerLM(cfg)
    spec = TrainerSpec.from_args(args, num_nodes=8, lr=0.01, grad_clip=1.0,
                                 graph="ring")
    k = spec.num_nodes
    seq = args.seq_len

    trainer = spec.build(model.loss, obs=sink)
    print(format_meta(sink.log(
        "meta", 0, arch=cfg.name, params=model.num_params(), nodes=k,
        rho=round(trainer.rho, 4), mu=args.mu, robust=spec.robust,
        compress=args.compress, topology=spec.topology,
        local_updates=spec.local_updates, steps=args.steps,
        sanitize=spec.sanitize, **_dynamics_meta(spec))))
    state = trainer.init(model.init(jax.random.PRNGKey(args.seed)))
    streams = make_node_token_streams(k, cfg.vocab, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    prefix = cfg.frontend_len if cfg.frontend != "token" else 0

    def sample_batch(step):
        toks = np.stack([
            s.next_batch(args.batch_per_node, seq) for s in streams])
        batch = {"tokens": toks}
        if prefix:
            batch["embeddings"] = rng.standard_normal(
                (k, args.batch_per_node, prefix, cfg.d_model)
            ).astype(np.float32) * 0.02
        return batch

    history = []
    t0 = time.time()
    compressed = trainer.compression is not None

    def on_segment(step, seg_state, ms):
        # the console line and the history entry are the SAME record the
        # in-graph tap delivered for this step — no parallel metrics path
        rec = sink.last("train")
        rec = dict(rec) if rec is not None else {"step": step}
        rec["wall_s"] = time.time() - t0
        history.append(rec)
        print(format_train(rec, compressed=compressed))

    with profile(args.log_dir, enabled=args.profile) as prof:
        state = run_segments(trainer, state, sample_batch, args.steps,
                             args.log_every, on_segment, obs=sink)
        sink.barrier()
    if prof.trace_path:
        print(f"profiler trace: {prof.trace_path}")
    if args.ckpt_dir:
        # full DecentralizedState incl. CommState (EF residuals, schedule
        # norms, dynamics tracking) — restore_train_state resumes bit-exactly
        save_train_state(args.ckpt_dir, args.steps, state)
        print(f"checkpoint saved to {args.ckpt_dir}")
    return history


def train_paper(args, sink: MetricsSink):
    exp = fmnist_default() if args.paper == "fmnist" else cifar_default()
    steps = args.steps or exp.steps
    if args.paper == "fmnist":
        ds = make_fmnist_like()
        params = mlp_init(jax.random.PRNGKey(args.seed))
        apply_fn = mlp_apply
    else:
        ds = make_cifar_like()
        params = cnn_init(jax.random.PRNGKey(args.seed))
        apply_fn = cnn_apply
    spec = TrainerSpec.from_args(
        args, num_nodes=exp.num_nodes, lr=exp.lr,
        graph="erdos_renyi", graph_kwargs={"p": exp.p, "seed": args.seed})
    k = spec.num_nodes
    fed = pathological_noniid_partition(ds, k, seed=args.seed)
    x_nodes, y_nodes = fed.per_node_test_sets(n_per_node=200, seed=args.seed)
    trainer = spec.build(make_classifier_loss(apply_fn), apply_fn, obs=sink)
    state = trainer.init(params)
    rng = np.random.default_rng(args.seed)
    bsz = args.batch_per_node or exp.batch_size
    print(format_meta(sink.log(
        "meta", 0, paper=args.paper, nodes=k, steps=steps, batch=bsz,
        lr=spec.lr, mu=args.mu, rho=round(trainer.rho, 4),
        compress=args.compress, topology=spec.topology,
        local_updates=spec.local_updates, sanitize=spec.sanitize,
        **_dynamics_meta(spec))))

    def sample_batch(step):
        xb, yb = fed.sample_batch(rng, bsz)
        return (xb, yb)

    def on_segment(step, seg_state, ms):
        # paper fairness metrics (worst-distribution accuracy, per-device
        # STDEV) into the telemetry stream, with the DR-weight snapshot of
        # the last train step riding along
        stats = trainer.eval_local_distributions(seg_state, x_nodes, y_nodes)
        # dr_weights is decimated (vector_every): take the newest record
        # that actually carries it, not the newest record
        train_rec = sink.last_with("train", "dr_weights")
        rec = sink.log(
            "eval", step,
            loss_mean=float(ms["loss_mean"][-1]),
            comm_bytes=float(ms["comm_bytes"][-1]),
            dr_weights=(train_rec or {}).get("dr_weights"),
            **stats)
        print(format_eval(rec))

    with profile(args.log_dir, enabled=args.profile) as prof:
        state = run_segments(trainer, state, sample_batch, steps,
                             args.log_every, on_segment, obs=sink)
        sink.barrier()
    if prof.trace_path:
        print(f"profiler trace: {prof.trace_path}")
    return state


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="assigned architecture id")
    ap.add_argument("--paper", default=None, choices=["fmnist", "cifar"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch-per-node", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    add_obs_cli_args(ap)
    TrainerSpec.add_cli_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    with MetricsSink(args.log_dir,
                     vector_every=args.tap_vectors_every) as sink:
        if args.paper:
            train_paper(args, sink)
        elif args.arch:
            train_lm(args, sink)
        else:
            raise SystemExit("provide --arch <id> or --paper fmnist|cifar")
        if sink.path:
            print(f"telemetry: {sink.path}")


if __name__ == "__main__":
    main()
