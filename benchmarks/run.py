"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Sections:
  1. Paper figures 2a/2b/3a/3b (the faithful reproduction; claim checks)
  2. Coded-matmul throughput / erasure sweep
  3. Kernel micro-benches (interpret-mode exactness + jnp twin timing)

One CSV-ish block per paper table/figure, per the harness contract.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="smaller job counts for CI-speed runs")
    ap.add_argument("--skip", default="",
                    help="comma list: figures,coded,kernels")
    args = ap.parse_args()
    skip = set(args.skip.split(",")) if args.skip else set()

    if "figures" not in skip:
        print("#" * 72)
        print("# paper figures (benchmarks/paper_figures.py)")
        from benchmarks import paper_figures
        paper_figures.run_all(fast=args.fast)

    if "coded" not in skip:
        print("#" * 72)
        print("# coded matmul (benchmarks/bench_coded_matmul.py)")
        from benchmarks import bench_coded_matmul
        bench_coded_matmul.main()

    if "kernels" not in skip:
        print("#" * 72)
        print("# kernels (benchmarks/bench_kernels.py)")
        from benchmarks import bench_kernels
        bench_kernels.main()


if __name__ == "__main__":
    main()
