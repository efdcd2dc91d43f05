#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic mix, limits
and per-layer metric readers are found by name (``bench/harness/common.py``).
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, the device's busy and window seconds and a breakdown,
read from a profile of the window.  Every run checks what the timed path
produced against the plain reference (``bench/reference``) and prints each
number compared beside its limit, last on stderr and last in the result.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench.harness import common

    spec = common.resolve(args.workload, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace))
    common.enable_compile_cache()
    devs = common.require_chip(spec)
    if spec.job["kind"] == "train":
        from bench.harness import train as runner
    else:
        from bench.harness import serve as runner
    result, checks = runner.run(spec, devs, T_START)
    common.emit(result, checks)


if __name__ == "__main__":
    main()
