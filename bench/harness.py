"""The data-driven harness: find a cell's files by name, run it, report.

``BENCHMARK.json`` names every cell, configuration and metric.  The
harness finds what belongs to each in a file of its own:

* ``configs[].file``             the configuration (public config keys);
* ``bench/traffic/<traffic>.json``  the traffic mix; its ``generator`` names
  the general generator in ``bench/generators/<generator>.py`` that runs it;
* ``bench/cells/<workload>.json``   the limits that decide ``correct``;
* ``bench/metrics/<metric>.py``     the reader of one per-layer metric,
  ``read(run) -> float | None``.

So a later cell, configuration, traffic mix or metric is a new file and
a new entry, with no edit to a file that exists.  A file that is missing
is an error that names it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
from typing import Any

from bench.dims import Dims

__all__ = ["Cell", "Run", "load_benchmark", "resolve", "device_info",
           "check_devices", "reader", "report"]

#: the benchmark's directory in a checkout, beside BENCHMARK.json
DIR = "bench"


class BenchError(RuntimeError):
    """A cell that cannot be run as declared."""


@dataclasses.dataclass
class Cell:
    name: str
    dir: pathlib.Path      # the checkout's bench directory
    chips: int
    config: dict
    dims: Dims
    traffic: dict
    limits: dict
    end_to_end: list       # metric entries of BENCHMARK.json this cell reports
    per_layer: list


@dataclasses.dataclass
class Run:
    """What one run of a cell measured; the per-layer readers read it."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float = math.nan
    end_to_end: dict = dataclasses.field(default_factory=dict)
    host: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    checks: list = dataclasses.field(default_factory=list)
    device: dict = dataclasses.field(default_factory=dict)
    trace: Any = None          # bench.trace.Reduction of a --trace 1 run
    peaks: Any = None          # bench.peaks.Peaks of the device
    control: bool = False      # the control stands in for the program
    window_compiles: int = 0

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.checks)


def _need(path: pathlib.Path, what: str) -> pathlib.Path:
    if not path.is_file():
        raise BenchError(f"{what}: missing file {path}")
    return path


def load_benchmark(root: pathlib.Path) -> dict:
    return json.loads(_need(root / "BENCHMARK.json",
                            "benchmark").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: pathlib.Path, bench: dict, workload: str) -> Cell:
    """The cell named ``workload``, with every file it needs read."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    here = root / DIR
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{workload}: no configuration {w['config']!r}")
    config = json.loads(_need(root / configs[w["config"]]["file"],
                              f"configuration {w['config']}").read_text())
    traffic = json.loads(_need(here / "traffic" / f"{w['traffic']}.json",
                               f"traffic {w['traffic']}").read_text())
    _need(here / "generators" / f"{traffic['generator']}.py",
          f"generator of traffic {w['traffic']}")
    limits = json.loads(_need(here / "cells" / f"{workload}.json",
                              f"limits of {workload}").read_text())
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    for m in per_layer:
        _need(here / "metrics" / f"{m['name']}.py", f"metric {m['name']}")
    return Cell(name=workload, dir=here, chips=int(w["chips"]),
                config=config,
                dims=Dims.from_config(config), traffic=traffic,
                limits=limits["limits"],
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=per_layer)


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(cell: Cell):
    d = cell.traffic["generator"]
    return _load_module(cell.dir / "generators" / f"{d}.py",
                        f"bench_generator_{d}")


def reader(cell: Cell, metric: str):
    return _load_module(cell.dir / "metrics" / f"{metric}.py",
                        f"bench_metric_{metric}").read


def device_info(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes
    in use on the fullest of ``devices``."""
    import jax
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    all_devs = jax.devices()
    return {"platform": all_devs[0].platform, "kind": all_devs[0].device_kind,
            "count": len(all_devs), "memory_peak_bytes": peak}


def check_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; exits non-zero on anything else."""
    import jax
    devs = jax.devices()
    print(f"[bench] devices: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", file=sys.stderr,
          flush=True)
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's device is {devs[0].platform!r}, "
                         f"not a TPU; the benchmark measures only on a TPU")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def _metric(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


def report(run: Run, *, trace: bool, out=None, err=None) -> dict:
    """Print the checks (last lines of stderr) and the result line (last
    line of stdout); returns the result."""
    out = out or sys.stdout
    err = err or sys.stderr
    metrics: dict[str, dict] = {}
    if trace:
        for m in run.cell.per_layer:
            v = reader(run.cell, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = _metric(v, m["unit"])
    else:
        for m in run.cell.end_to_end:
            if m["name"] not in run.end_to_end:
                raise BenchError(f"{run.cell.name}: the run did not "
                                 f"measure {m['name']}")
            metrics[m["name"]] = _metric(run.end_to_end[m["name"]],
                                         m["unit"])
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics,
              "device": run.device, "window_compiles": run.window_compiles}
    if trace and run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in run.checks}
    for name, v, lim in run.checks:
        print(f"[check] {name} {v!r} limit {lim!r} "
              f"{'ok' if math.isfinite(v) and v <= lim else 'FAIL'}",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result
