"""Beyond-paper Fig. 11: decentralized gossip vs federated hub vs
hierarchical consensus at equal cumulative wire bytes.

The Topology × Transport × Wire refactor makes the decentralized↔federated
axis a config change: the star transport is one more layer stack behind the
same v2 ``Mixer`` protocol, so the planned head-to-head runs on the
unchanged fig8/fig9 machinery.  Rows (fmnist, pathological non-IID split,
DR-DSGD μ = 3):

* **gossip ring (K = 8)** — the paper's decentralized lowering: one
  ppermute per ring matching, O(deg·P) bytes per node per round, consensus
  contracts at the ring's spectral gap.
* **hub H = 1 (K = 8)** — every round is the exact server average
  (W = 11ᵀ/K, the ρ = 0 endpoint): K uploads + K downloads per round, the
  most wire per round and the fastest consensus (disagreement snaps to
  float noise each round).
* **hub H = 4 (FedAvg)** — ``LocalUpdateMixer(make_hub_mixer(K), 4)``: 4 local
  steps between server rounds cuts cumulative wire 4× at the price of
  client drift under the non-IID split.
* **hub H = 4 + gradient tracking (SCAFFOLD)** — the tracker correction
  under W = 11ᵀ/K is exactly SCAFFOLD's control variate c_i; same wire as
  FedAvg, drift removed.
* **hierarchical (K = 4 × R = 2)** — psum-mean inside each node, gossip
  across: the consensus wire scales with K, not the device count — the
  K ≪ world-size regime of multi-100B training.

Equal-wire comparison: every row reports worst-distribution accuracy at the
smallest cumulative wire-byte budget any compared run consumed
(``acc@budget``), the same protocol as fig9's codec rows.  The hub-H1 row
asserts the exact-consensus property (final disagreement at float noise);
every row asserts the zero-recompile invariant (one compiled scan program)
via the shared ``RecompileWatchdog`` inside ``run_decentralized``.

Output rows: ``name,us_per_step,<derived>``; results recorded in
EXPERIMENTS.md §Comm-architecture.
"""

from __future__ import annotations

import os

# the gossip/hierarchical rows shard one node (× replica) per device; force
# the host platform to expose 8 devices BEFORE jax initializes
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

from benchmarks.common import fmt_row, run_decentralized


def _run(steps, eval_every, seed, num_nodes=8, **kw):
    return run_decentralized(
        "fmnist", robust=True, mu=3.0, num_nodes=num_nodes, steps=steps,
        batch=55, lr=0.18, graph="ring", seed=seed, eval_every=eval_every,
        lr_compensate=False, **kw)


def _acc_at_bytes(history, budget: float) -> float | None:
    """Worst-distribution accuracy at the last eval within a byte budget."""
    acc = None
    for h in history:
        if h["cum_bytes"] <= budget * (1 + 1e-6):
            acc = h["acc_worst_dist"]
    return acc


def run(steps: int = 400, eval_every: int = 50, seed: int = 0,
        smoke: bool = False) -> list[str]:
    runs = []

    # decentralized baseline: ppermute gossip on the static ring
    r = _run(steps, eval_every, seed, lowering="gossip", topology="static")
    r["label"] = "fig11_gossip_ring_k8"
    runs.append(r)

    # federated lowerings: the star stack on the dense path
    hub_cfgs = [("fig11_hub_H1", 1, False)] if not smoke else []
    hub_cfgs += [("fig11_hub_H4_fedavg", 4, False),
                 ("fig11_hub_H4_scaffold", 4, True)]
    for label, h, gt in hub_cfgs:
        r = _run(steps, eval_every, seed, topology="hub", local_updates=h,
                 gradient_tracking=gt)
        r["label"] = label
        runs.append(r)

    # hierarchical: replica psum inside each of 4 nodes, gossip across
    r = _run(steps, eval_every, seed, num_nodes=4, lowering="hierarchical",
             replicas=2)
    r["label"] = "fig11_hier_k4x2"
    runs.append(r)

    # equal-wire protocol: accuracy at the smallest cumulative byte budget
    # any run consumed (hub H=4 spends 1/4 of H=1's rounds on the wire, the
    # hierarchical row wires K=4 blocks instead of 8)
    budget = min(r["comm_bytes_total"] for r in runs)
    for r in runs:
        r["acc_at_budget"] = _acc_at_bytes(r["history"], budget)

    # the ρ = 0 endpoint: a server round IS the average — final
    # disagreement sits at float noise, not at a spectral-gap floor
    hub1 = next((r for r in runs if r["label"] == "fig11_hub_H1"), None)
    if hub1 is not None:
        assert hub1["disagreement_final"] < 1e-6, (
            "hub H=1 must reach exact consensus every round: "
            f"disagreement {hub1['disagreement_final']:.3e}")

    rows = []
    for r in runs:
        acc_b = r.get("acc_at_budget")
        rows.append(fmt_row(
            r["label"], r["us_per_step"],
            f"acc_worst={r['acc_worst_dist']:.3f};"
            f"acc_avg={r['acc_avg']:.3f};"
            f"acc@{budget:.2e}B="
            + (f"{acc_b:.3f}" if acc_b is not None else "n/a")
            + f";consensus_err={r['disagreement_final']:.3e};"
            f"bytes_total={r['comm_bytes_total']:.3e};"
            f"programs={r['run_programs']}"))
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (one row per transport; "
                         "plumbing + the zero-recompile assertion, not "
                         "converged accuracy)")
    args = ap.parse_args()
    steps = 30 if args.smoke else args.steps
    eval_every = 15 if args.smoke else args.eval_every
    print("\n".join(run(steps=steps, eval_every=eval_every, seed=args.seed,
                        smoke=args.smoke)))


if __name__ == "__main__":
    main()
