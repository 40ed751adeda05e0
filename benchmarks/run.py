"""Benchmark driver — one module per paper table/figure + kernel micro-bench.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,...]

Prints ``name,us_per_call,derived`` CSV rows.  Default mode is scaled down to
finish on a CPU container; --full approaches the paper's setting (100
clients, 300+ rounds) and is intended for real hardware.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    fig1_convergence,
    fig2_ablations,
    kernels_bench,
    round_bench,
    table1_accuracy,
    table2_modules,
)

SUITES = {
    "table1": table1_accuracy.main,
    "fig1": fig1_convergence.main,
    "fig2": fig2_ablations.main,
    "table2": table2_modules.main,
    "kernels": kernels_bench.main,
    "round": round_bench.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale (hours); default is CPU-scaled")
    ap.add_argument("--fast", action="store_true",
                    help="explicit alias for the default CPU-scaled mode")
    ap.add_argument("--only", default=None, help="comma list of suites")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for name in only:
        t0 = time.time()
        try:
            SUITES[name](fast=not args.full)
        except Exception as e:  # run the other suites, then exit nonzero
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", file=sys.stdout)
            failed.append(name)
        print(f"# suite {name} done in {time.time() - t0:.0f}s", flush=True)
    if failed:
        sys.exit(f"failed suites: {','.join(failed)}")


if __name__ == "__main__":
    main()
