"""Benchmark harness: one entry per paper table/figure + the roofline table.

Prints ``name,us_per_call,derived`` CSV rows (see repo scaffold contract).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig2,roofline
"""

from __future__ import annotations

import argparse
import time

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig2,fig3,fig4,table1,"
                         "fig5,fig6,fig7,fig8,roofline")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (
        fig2_fmnist_robustness,
        fig3_cifar_robustness,
        fig4_fairness,
        fig5_sparsity,
        fig6_topology,
        fig7_compression,
        fig8_adaptive,
        roofline,
        table1_mu_tradeoff,
    )

    suites = {
        "fig2": fig2_fmnist_robustness.run,
        "fig3": fig3_cifar_robustness.run,
        "fig4": fig4_fairness.run,
        "table1": table1_mu_tradeoff.run,
        "fig5": fig5_sparsity.run,
        "fig6": fig6_topology.run,
        "fig7": fig7_compression.run,
        "fig8": fig8_adaptive.run,
        "roofline": roofline.run,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if name not in only:
            continue
        t0 = time.perf_counter()
        try:
            for row in fn():
                print(row, flush=True)
            print(f"{name}_suite_wall,{(time.perf_counter() - t0) * 1e6:.0f},ok",
                  flush=True)
        except Exception as e:  # keep the harness running; report the failure
            print(f"{name}_suite_wall,0,FAILED:{e!r}", flush=True)
            raise


if __name__ == '__main__':
    main()
