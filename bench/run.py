"""Run one benchmark cell once, on a TPU, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic and
metrics are found by name through ``BENCHMARK.json`` (``bench/harness.py``).
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the measured window and
from host clocks.  The last line of standard output is one JSON object;
the last lines of standard error are the numbers the correctness check
compared, each beside its limit.  Exits non-zero, before measuring,
unless JAX's devices are TPUs and there are as many as the cell needs.
"""

from __future__ import annotations

import time

#: process start, as near as this program can take it: set-up is timed
#: from here to the first timed request
T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# libtpu logs into the checkout, not into a fixed directory of the host
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))

from bench import harness  # noqa: E402

#: where a --trace 1 run writes its profile, inside the checkout
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_tpu: bool = True, root: pathlib.Path = ROOT,
         bench: dict | None = None, t_start: float = T_START) -> int:
    """One run.  ``require_tpu=False`` is for the tests alone: they drive
    the rest of a run on the CPU, where no device number means anything."""
    args = parse(argv)
    if bench is None:
        bench = harness.load_benchmark(root)
    cell = harness.resolve(root, bench, args.workload)

    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    # small programs too: every one the window drives is found again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    if require_tpu:
        devices = harness.check_devices(cell.chips)
        from bench.peaks import peaks_for
        peaks = peaks_for(devices[0].device_kind)
    else:
        devices, peaks = jax.devices()[:cell.chips], None
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      peaks=peaks)
    trace_dir = None
    if args.trace:
        trace_dir = root / TRACE_DIR.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        harness.generator(cell).run(run, devices, t_start=t_start,
                                 trace_dir=trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    harness.report(run, trace=bool(args.trace))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
