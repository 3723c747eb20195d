"""Block-size autotune sweep for the layered-matmul Pallas kernel.

Times ``layered_matmul_kernel_call`` over a small (bm, bn, bk) grid on a
given problem shape and reports the fastest legal configuration.  The
kernel runs compiled (Mosaic, megacore-parallel M/N grid), which needs a
TPU.  ``--interpret`` runs it in the Pallas interpreter instead, on any
backend: that sweep validates the BlockSpecs and the relative block-count
trade-offs, not MXU throughput.

Run:  PYTHONPATH=src python benchmarks/bench_kernel_autotune.py \
          --m 2 --d 7 --K 1024 --M 256 --N 256 --repeats 3 [--interpret]
"""

from __future__ import annotations

import argparse
import itertools
import json
import pathlib
import time

import jax
import numpy as np

from repro.kernels.layered_matmul import layered_matmul_kernel_call

BM_SWEEP = (128, 256)
BN_SWEEP = (128, 256)
BK_SWEEP = (256, 512, 1024)


def candidate_blocks(M: int, N: int, K: int) -> list[tuple[int, int, int]]:
    """Legal (bm, bn, bk) triples: divisors of the problem dims."""
    bms = [b for b in BM_SWEEP if M % b == 0] or [M]
    bns = [b for b in BN_SWEEP if N % b == 0] or [N]
    bks = [b for b in BK_SWEEP if K % b == 0] or [K]
    return list(itertools.product(bms, bns, bks))


def time_config(pa, pb, *, m: int, d: int, bm: int, bn: int, bk: int,
                interpret: bool, repeats: int) -> float:
    """Median seconds per call (after one warm-up/compile call)."""
    call = lambda: layered_matmul_kernel_call(
        pa, pb, m=m, d=d, bm=bm, bn=bn, bk=bk,
        interpret=interpret).block_until_ready()
    call()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        call()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def sweep(*, m: int, d: int, K: int, M: int, N: int, repeats: int,
          interpret: bool, seed: int = 0) -> list[dict]:
    rng = np.random.default_rng(seed)
    hi = 1 << (d - 1)
    pa = jax.numpy.asarray(rng.integers(-hi, hi, size=(m, K, M)),
                           jax.numpy.int8)
    pb = jax.numpy.asarray(rng.integers(-hi, hi, size=(m, K, N)),
                           jax.numpy.int8)
    rows = []
    for bm, bn, bk in candidate_blocks(M, N, K):
        sec = time_config(pa, pb, m=m, d=d, bm=bm, bn=bn, bk=bk,
                          interpret=interpret, repeats=repeats)
        rows.append({"bm": bm, "bn": bn, "bk": bk,
                     "grid": [M // bm, N // bn, K // bk],
                     "seconds": sec})
        print(f"  bm={bm:>4} bn={bn:>4} bk={bk:>5}  "
              f"grid={M // bm}x{N // bn}x{K // bk}  {sec * 1e3:9.3f} ms")
    rows.sort(key=lambda r: r["seconds"])
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--d", type=int, default=7)
    ap.add_argument("--K", type=int, default=1024)
    ap.add_argument("--M", type=int, default=256)
    ap.add_argument("--N", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernel in the Pallas interpreter "
                         "(any backend; no MXU timing)")
    ap.add_argument("--json", default=None, help="write sweep rows here")
    args = ap.parse_args(argv)

    interpret = args.interpret
    mode = "interpret" if interpret else "compiled"
    print(f"layered_matmul autotune ({mode}): m={args.m} d={args.d} "
          f"K={args.K} M={args.M} N={args.N}")
    rows = sweep(m=args.m, d=args.d, K=args.K, M=args.M, N=args.N,
                 repeats=args.repeats, interpret=interpret)
    best = rows[0]
    print(f"best: bm={best['bm']} bn={best['bn']} bk={best['bk']} "
          f"({best['seconds'] * 1e3:.3f} ms)")
    if args.json:
        path = pathlib.Path(args.json)
        path.write_text(json.dumps(
            {"bench": "layered_matmul_autotune", "mode": mode,
             "shape": {"m": args.m, "d": args.d, "K": args.K, "M": args.M,
                       "N": args.N},
             "rows": rows}, indent=2))
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
