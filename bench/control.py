"""The readings that set a cell's correctness limits, and its control.

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3

For each seed it makes one run of the cell at its own sizes, with a
window just long enough to finish the requests that a run compares, and
prints the run's result line: the program's readings of every number the
check compares, beside its limit.  On the first ``--control-seeds``
seeds it makes a second run with the control in the program's place
(``Run.control``): the reference computed in float8 (e4m3, one scale per
tensor), the precision below the configuration's bfloat16, which has to
come out ``correct: false`` under the cell's limits.  The lower reading
of a number is the largest of the program's runs, the upper one the
smallest of the control's.

Benchmark runs never run this; it is run on the chip when a limit is
set, and ``bench/tests/test_control.py`` runs it at a size a test can
hold.  The last line of standard output is one JSON object of every
reading.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# libtpu logs into the checkout, not into a fixed directory of the host
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))

from bench import harness  # noqa: E402

__all__ = ["readings"]


def readings(root: pathlib.Path, workload: str, seeds: list[int], *,
             control_seeds: int, seconds: float = 0.0,
             require_tpu: bool = True) -> list[dict]:
    """One result per run: the program's on every seed, the control's on
    the first ``control_seeds``; each with ``seed`` and ``control``."""
    import jax
    from repro.launch import compile_cache
    from bench.window import clock
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.resolve(root, harness.load_benchmark(root), workload)
    if require_tpu:
        devices = harness.check_devices(cell.chips)
        from bench.peaks import peaks_for
        peaks = peaks_for(devices[0].device_kind)
    else:
        devices, peaks = jax.devices()[:cell.chips], None
    gen = harness.generator(cell)
    rows = []
    for i, seed in enumerate(seeds):
        for control in (False, True)[:2 if i < control_seeds else 1]:
            run = harness.Run(cell=cell, seed=seed, seconds=seconds,
                              peaks=peaks, control=control)
            gen.run(run, devices, t_start=clock())
            res = harness.report(run, trace=False)
            rows.append({"seed": seed, "control": control,
                         "correct": res["correct"],
                         "checks": res["checks"]})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_019)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window of each run; the shortest still holds "
                         "the requests a run compares")
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = readings(ROOT, args.workload, seeds,
                    control_seeds=args.control_seeds, seconds=args.seconds)
    print(json.dumps({"workload": args.workload, "readings": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
