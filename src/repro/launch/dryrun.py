import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

For each combination this produces, WITHOUT allocating any model memory:
  * proof that the distribution config lowers and compiles (the deliverable),
  * ``memory_analysis()``  — per-device argument/output/temp bytes,
  * ``cost_analysis()``    — HLO FLOPs / bytes accessed,
  * collective wire bytes  — parsed from the compiled HLO text,
  * scan-trip-count-corrected totals: XLA's cost analysis counts a `while`
    body once, so two *unrolled* probe lowers with 1 and 2 pattern groups fit
    cost(G) = a + b*G, extrapolated to the real group count.

Shapes: train_4k lowers the decentralized DR-DSGD train_step (node axis =
"data" / ("pod","data")); prefill_32k lowers `prefill`; decode shapes lower
`serve_step` (one token against the KV/recurrent cache). `long_500k` runs
only for sub-quadratic archs (ssm / hybrid / SWA-only) per the task spec.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --mixer dense --out experiments/dryrun
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.dynamics import TOPOLOGY_KINDS
from repro.core import (
    CompressionConfig, RobustConfig, TrainStepConfig,
    add_compression_cli_args, build_train_step, compression_from_args,
    make_dense_mixer, make_gossip_mixer,
)
from repro.core.drdsgd import DecentralizedState
from repro.graphs import (
    build_graph, metropolis_weights, permutation_decomposition,
)
from repro.launch.mesh import make_production_mesh, node_axes, num_nodes
from repro.models import SHAPES, TransformerLM, input_shapes
from repro.obs import expect_compiles
from repro.models.config import ArchConfig, ShapeConfig
from repro.optim import sgd
from repro.launch.mesh import make_auto_mesh
from repro.utils.hlo import collective_summary, parse_collectives
from repro.utils.roofline import model_flops


def runs_shape(cfg: ArchConfig, shape: ShapeConfig) -> bool:
    if shape.name == "long_500k":
        return cfg.arch_type in ("ssm", "hybrid") or cfg.is_subquadratic
    return True


def _node_stack_shapes(tree, k: int):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((k,) + s.shape, s.dtype), tree)


def _shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# -- builders per execution mode ---------------------------------------------

def build_train(cfg: ArchConfig, shape: ShapeConfig, mesh, mixer_kind: str,
                graph_kind: str = "ring",
                compression: CompressionConfig | None = None,
                topology: str = "dropout", drop_p: float = 0.2,
                ef_rebase_every: int = 8):
    """Returns (fn, example_args, in_shardings)."""
    model = TransformerLM(cfg)
    hier = "fsdp" in mesh.axis_names
    k = num_nodes(mesh)
    naxes = node_axes(mesh)
    node_axis = naxes[0] if len(naxes) == 1 else tuple(naxes)
    g = build_graph(graph_kind, k)
    w = metropolis_weights(g)
    pspecs = model.param_specs(
        mesh, mode="train_fsdp" if hier else "train", node_axis=node_axis)
    if mixer_kind == "dense":
        mixer = make_dense_mixer(w, compression=compression)
    elif mixer_kind == "gossip":
        mixer = make_gossip_mixer(
            permutation_decomposition(w), mesh, node_axis, pspecs,
            compression=compression)
    elif mixer_kind == "gossip-dynamic":
        # time-varying topology lowering (repro.dynamics): static ppermute
        # structure over the union support, traced per-round weights/masks.
        # An error-feedback config builds the EF wire with periodic hat_mix
        # re-basing (DynamicCompressedGossipMixer, --ef-rebase-every);
        # --no-error-feedback keeps the memoryless masked int8 kernel wire.
        from repro.dynamics import DynamicGossipMixer, make_schedule

        if (compression is not None and compression.enabled
                and not compression.error_feedback
                and compression.kind not in ("int8", "int4")):
            raise ValueError(
                "the memoryless gossip-dynamic wire serves --compress "
                "int8/int4 (masked kernel wire, traced qmax); "
                "error-feedback configs take any codec")
        mixer = DynamicGossipMixer(
            make_schedule(topology, w=w, k=k, drop_p=drop_p),
            mesh, node_axis, pspecs, quantized=compression,
            ef_rebase_every=ef_rebase_every)
    else:
        raise ValueError(mixer_kind)
    step_cfg = TrainStepConfig(
        robust=RobustConfig(mu=6.0), metrics_disagreement=False,
        compression=compression)
    train_step = build_train_step(model.loss, sgd(1e-2), mixer, step_cfg)

    params = _node_stack_shapes(model.param_shapes(), k)
    # uniform Mixer protocol: every mixer allocates (and shards) a CommState
    comm = jax.eval_shape(mixer.init_state, params)
    state = DecentralizedState(
        params=params, opt_state=(), step=jax.ShapeDtypeStruct((), jnp.int32),
        comm=comm)
    batch = input_shapes(cfg, shape, num_nodes=k)

    comm_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), mixer.state_specs(pspecs),
        is_leaf=lambda x: isinstance(x, P))
    state_sh = DecentralizedState(
        params=_shardings(mesh, pspecs),
        opt_state=(),
        step=NamedSharding(mesh, P()),
        comm=comm_sh,
    )
    # hierarchical mode: the per-node batch dim is FSDP data-parallel
    inner = "fsdp" if hier else None
    batch_sh = jax.tree.map(
        lambda s: NamedSharding(
            mesh, P(node_axis, inner, *([None] * (len(s.shape) - 2)))),
        batch)
    fn = jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                 out_shardings=(state_sh, None))
    return fn, (state, batch)


def build_prefill(cfg: ArchConfig, shape: ShapeConfig, mesh):
    model = TransformerLM(cfg)
    daxes = node_axes(mesh)
    dax = daxes[0] if len(daxes) == 1 else tuple(daxes)
    pspecs = model.param_specs(mesh, mode="serve")
    batch = input_shapes(cfg, shape)
    batch_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, P(dax, *([None] * (len(s.shape) - 1)))),
        batch)
    fn = jax.jit(model.prefill,
                 in_shardings=(_shardings(mesh, pspecs), batch_sh))
    return fn, (model.param_shapes(), batch)


def build_decode(cfg: ArchConfig, shape: ShapeConfig, mesh):
    model = TransformerLM(cfg)
    daxes = node_axes(mesh)
    dax = daxes[0] if len(daxes) == 1 else tuple(daxes)
    b, s = shape.global_batch, shape.seq_len
    pspecs = model.param_specs(mesh, mode="serve")
    cache_shapes = jax.eval_shape(lambda: model.init_cache(b, s))
    cache_specs = model.cache_pspecs(b, s, mesh, dax)
    inputs = input_shapes(cfg, shape)
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    tok_spec = P(dax, None) if b % dsize == 0 else P(None, None)
    in_sh = (
        _shardings(mesh, pspecs),
        NamedSharding(mesh, tok_spec),
        NamedSharding(mesh, P()),
        _shardings(mesh, cache_specs),
    )
    fn = jax.jit(model.decode_step, in_shardings=in_sh, donate_argnums=(3,))
    args = (model.param_shapes(), inputs["token"], inputs["pos"], cache_shapes)
    return fn, args


def build_fn(cfg, shape, mesh, mixer_kind, graph_kind="ring",
             compression=None, topology="dropout", drop_p=0.2,
             ef_rebase_every=8):
    if shape.kind == "train":
        return build_train(cfg, shape, mesh, mixer_kind, graph_kind,
                           compression, topology=topology, drop_p=drop_p,
                           ef_rebase_every=ef_rebase_every)
    if shape.kind == "prefill":
        return build_prefill(cfg, shape, mesh)
    return build_decode(cfg, shape, mesh)


# -- compile + measure ---------------------------------------------------------

def _cost_entries(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def compile_and_measure(cfg, shape, mesh, mixer_kind, want_hlo=True,
                        graph_kind="ring", compression=None,
                        topology="dropout", drop_p=0.2, ef_rebase_every=8,
                        audit=False):
    fn, args = build_fn(cfg, shape, mesh, mixer_kind, graph_kind, compression,
                        topology=topology, drop_p=drop_p,
                        ef_rebase_every=ef_rebase_every)
    t0 = time.time()
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    out = {
        "lower_s": t_lower,
        "compile_s": t_compile,
        "cost": _cost_entries(compiled),
    }
    ma = compiled.memory_analysis()
    out["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }
    if want_hlo:
        txt = compiled.as_text()
        colls = parse_collectives(txt, world_size=mesh.devices.size)
        out["collectives"] = collective_summary(colls)
    if audit:
        # static-analysis pass over the program that just compiled: stray
        # host callbacks (anything outside repro.obs) and scalar baked
        # constants (recompile hazards) — repro.analysis.audit
        from repro.analysis.audit import (
            audit_baked_consts, audit_host_callbacks,
        )

        closed = jax.make_jaxpr(fn)(*args)
        findings = (audit_host_callbacks(closed)
                    + audit_baked_consts(closed))
        out["audit"] = [str(f) for f in findings]
        errors = [f for f in findings if f.severity == "error"]
        if errors:
            raise RuntimeError(
                "audit errors in compiled program: "
                + "; ".join(str(f) for f in errors))
    return out


def _with_groups(cfg: ArchConfig, g: int, keep_chunking: bool = False
                 ) -> ArchConfig:
    """Probe variant: g pattern groups, fully unrolled AND unchunked.

    Unrolled: `lax.scan` bodies are counted once by XLA's cost analysis, so
    trip counts must not hide in while-loops.  Unchunked: the chunked
    attention / CE paths scan over blocks for memory reasons; probes raise
    the chunk sizes so each becomes a single (counted) block.  The remaining
    inner recurrences (mamba/rwkv time scans) stay undercounted but their
    FLOPs are negligible vs the projections (see EXPERIMENTS.md §Roofline
    conventions).  Consequence: probe "bytes" include the S^2 attention
    score traffic a fused flash kernel avoids — the memory term is an upper
    bound for attention-heavy shapes (quantified in §Perf).
    """
    big = 1 << 30
    n_layers = cfg.first_k_dense + cfg.pattern_len * g
    if keep_chunking:
        return dataclasses.replace(cfg, n_layers=n_layers, scan_layers=False)
    return dataclasses.replace(
        cfg, n_layers=n_layers, scan_layers=False,
        attn_q_chunk=big, attn_kv_chunk=big, logits_chunk=big)


def fit_scan_correction(cfg, shape, mesh, mixer_kind, graph_kind="ring",
                        compression=None, keep_chunking=False,
                        topology="dropout", drop_p=0.2, ef_rebase_every=8):
    """Unrolled G=1 / G=2 probes -> cost(G) = a + b*G, evaluated at n_groups."""
    probes = {}
    for g in (1, 2):
        r = compile_and_measure(
            _with_groups(cfg, g, keep_chunking=keep_chunking), shape, mesh,
            mixer_kind, graph_kind=graph_kind, compression=compression,
            topology=topology, drop_p=drop_p, ef_rebase_every=ef_rebase_every)
        probes[g] = {
            "flops": r["cost"]["flops"],
            "bytes": r["cost"]["bytes"],
            "wire_bytes": r["collectives"]["total_wire_bytes"],
        }
    n = cfg.n_groups
    fitted = {}
    for key in ("flops", "bytes", "wire_bytes"):
        b = probes[2][key] - probes[1][key]
        a = probes[1][key] - b
        fitted[key] = a + b * n
        fitted[f"{key}_per_group"] = b
        fitted[f"{key}_head"] = a
    fitted["probes"] = probes
    return fitted


def run_one(arch: str, shape_name: str, multi_pod: bool, mixer_kind: str,
            out_dir: str, skip_existing: bool = True, graph_kind: str = "ring",
            compression=None, compute_dtype=None, moe_constraints: bool = False,
            keep_chunking: bool = False, variant: str = "",
            hier_nodes: int = 0, remat_policy: str = "",
            topology: str = "dropout", drop_p: float = 0.2,
            ef_rebase_every: int = 8, audit: bool = False) -> dict | None:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    label = mixer_kind + (f"+{compression.kind}" if compression else "") \
        + (f"+sched-{compression.schedule.kind}"
           if compression and compression.schedule else "") \
        + (f"+{variant}" if variant else "")
    tag = f"{arch}__{shape_name}__{mesh_name}__{label}"
    path = os.path.join(out_dir, tag + ".json")
    if skip_existing and os.path.exists(path):
        print(f"[skip] {tag} (exists)")
        with open(path) as f:
            return json.load(f)
    if not runs_shape(cfg, shape):
        print(f"[skip] {tag}: long_500k needs sub-quadratic attention "
              f"({cfg.name} is full-attention; see DESIGN.md)")
        return None

    if hier_nodes:
        total = 512 if multi_pod else 256
        fsdp = total // (hier_nodes * 16)
        mesh = make_auto_mesh(
            (hier_nodes, fsdp, 16), ("data", "fsdp", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    if compute_dtype is not None:
        cfg = dataclasses.replace(cfg, compute_dtype=compute_dtype)
    if remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    if moe_constraints and cfg.moe is not None:
        daxes = node_axes(mesh)
        dax = daxes[0] if len(daxes) == 1 else tuple(daxes)
        if moe_constraints == "capacity":
            espec = P(None, dax, None)       # shard expert capacity dim
        else:
            ok = cfg.moe.num_experts % int(
                np.prod([mesh.shape[a] for a in daxes])) == 0
            espec = P(dax if ok else None, None, None)  # expert parallelism
        cfg = dataclasses.replace(
            cfg, moe_dispatch_specs=(
                NamedSharding(mesh, P(dax, None)),
                NamedSharding(mesh, espec)))
    model = TransformerLM(cfg)
    print(f"[run ] {tag}: {model.num_params()/1e9:.2f}B params ...", flush=True)
    # recompile watchdog on the AOT path (no jit cache to snapshot —
    # lower().compile() never populates one): one combination performs
    # exactly 3 genuine backend compiles (the full program + the two
    # unrolled G=1/G=2 probes).  The budget carries slack because the
    # monitoring counter also sees first-touch eager-op compiles and
    # per-compile event fan-out; a traced operand leaking into program
    # structure shows up as O(n_groups) extra compiles, far past 16.
    with expect_compiles(at_most=16, label=tag):
        res = compile_and_measure(cfg, shape, mesh, mixer_kind,
                                  graph_kind=graph_kind,
                                  compression=compression,
                                  topology=topology, drop_p=drop_p,
                                  ef_rebase_every=ef_rebase_every,
                                  audit=audit)
        fitted = fit_scan_correction(cfg, shape, mesh, mixer_kind,
                                     graph_kind=graph_kind,
                                     compression=compression,
                                     keep_chunking=keep_chunking,
                                     topology=topology, drop_p=drop_p,
                                     ef_rebase_every=ef_rebase_every)

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = model_flops(model.num_params(), tokens,
                     "train" if shape.kind == "train" else "serve",
                     active_params=model.num_active_params())
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "mixer": label,
        "graph": graph_kind,
        "variant": variant,
        "chips": int(mesh.devices.size),
        "num_nodes": num_nodes(mesh) if shape.kind == "train" else None,
        "params": model.num_params(),
        "active_params": model.num_active_params(),
        "tokens": tokens,
        "model_flops": mf,
        "n_groups": cfg.n_groups,
        "full": res,
        "fitted": fitted,
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    mem = res["memory"]
    print(f"       compile={res['compile_s']:.1f}s "
          f"arg={mem['argument_bytes']/1e9:.2f}GB temp={mem['temp_bytes']/1e9:.2f}GB "
          f"flops_fit={fitted['flops']:.3e} wire_fit={fitted['wire_bytes']:.3e}",
          flush=True)
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--mixer", default="dense",
                    choices=["dense", "gossip", "gossip-dynamic"])
    # geometric is excluded: its support moves every round, so only the
    # dense lowering can run it; hub is excluded: the star consensus has no
    # per-round schedule, it lowers through the dense path (make_hub_mixer)
    ap.add_argument("--topology", default="dropout",
                    choices=[k for k in TOPOLOGY_KINDS
                             if k not in ("geometric", "hub")],
                    help="gossip-dynamic: per-round topology schedule")
    ap.add_argument("--drop-p", type=float, default=0.2,
                    help="gossip-dynamic: link dropout probability")
    ap.add_argument("--ef-rebase-every", type=int, default=8,
                    help="gossip-dynamic: hat_mix re-base period B of the "
                         "error-feedback compressed wire (0 = never; "
                         "static schedules only)")
    ap.add_argument("--graph", default="ring")
    add_compression_cli_args(ap)
    ap.add_argument("--compute-dtype", default=None, choices=[None, "bf16"])
    ap.add_argument("--moe-constraints", default=None,
                    choices=[None, "expert", "capacity"])
    ap.add_argument("--keep-chunking", action="store_true",
                    help="probe with the chunked attention/CE paths (memory-"
                         "realistic bytes; see §Perf)")
    ap.add_argument("--variant", default="",
                    help="label suffix for the output file")
    ap.add_argument("--hier-nodes", type=int, default=0,
                    help="hierarchical mode: K nodes x (chips/16K) FSDP x 16 TP")
    ap.add_argument("--remat-policy", default="", choices=["", "full", "dots"])
    ap.add_argument("--audit", action="store_true",
                    help="run the repro.analysis.audit static passes (host "
                         "callbacks, baked scalar consts) over each compiled "
                         "combination; errors fail the combination")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    compression = compression_from_args(args)
    comp = jnp.bfloat16 if args.compute_dtype == "bf16" else None

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                try:
                    run_one(arch, shape, multi, args.mixer, args.out,
                            skip_existing=not args.force,
                            graph_kind=args.graph, compression=compression,
                            compute_dtype=comp,
                            moe_constraints=args.moe_constraints,
                            keep_chunking=args.keep_chunking,
                            variant=args.variant,
                            hier_nodes=args.hier_nodes,
                            remat_policy=args.remat_policy,
                            topology=args.topology, drop_p=args.drop_p,
                            ef_rebase_every=args.ef_rebase_every,
                            audit=args.audit)
                except Exception as e:  # a failure here is a sharding bug
                    failures.append((arch, shape, multi, repr(e)))
                    print(f"[FAIL] {arch} {shape} multi={multi}: {e!r}",
                          flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nAll dry-run combinations lowered and compiled successfully.")


if __name__ == "__main__":
    main()
