"""The harness finds every file by name, and a cell is added as files."""

import hashlib
import json
import math
import os
import re
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny
from bench.trace import Reduction

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.resolve(REPO, BENCH, cell)
    assert c.limits and c.traffic["generator"] == "serve"
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(harness.reader(c, m["name"]))


def test_benchmark_json_keeps_the_contract():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 seconds
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and NAME.match(c["name"])
        assert (REPO / c["file"]).is_file()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {"setup_s"} <= {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_missing_file_is_named(tmp_path):
    root = tiny.make_checkout(tmp_path)
    gone = root / "bench" / "traffic" / "chat-decode.json"
    gone.unlink()
    with pytest.raises(harness.BenchError, match=re.escape(str(gone))):
        harness.resolve(root, harness.load_benchmark(root),
                        "yi6b-chat-decode")
    for gone in (root / "bench" / "metrics" / "prefill_ms.py",
                 root / "bench" / "cells" / "starcoder2-code-prefill.json"):
        gone.unlink()
        with pytest.raises(harness.BenchError, match=re.escape(str(gone))):
            harness.resolve(root, harness.load_benchmark(root),
                            "starcoder2-code-prefill")


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_cell_added_as_new_files(tmp_path, monkeypatch):
    """A new configuration, traffic mix, cell and per-layer metric are new
    files and new entries; no file of the benchmark changes."""
    before = _digests(REPO)
    root = tiny.make_checkout(tmp_path)
    (root / "bench" / "metrics" / "requests_done.py").write_text(
        "def read(run):\n    return float(run.host['requests'])\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "requests_done", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "progressive server",
                           "moves": "tokens_per_s",
                           "workloads": ["tiny-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())
    # a CPU run has no device trace: hand the readers a made-up one
    monkeypatch.setattr(
        "bench.window.Window.reduce", lambda self: Reduction(
            window_s=1.0, busy_s=0.5, busy_by_device={"d": 0.5},
            module_s={"jit__lambda": 0.1}, op_s={"dot": 0.5},
            gaps=[("bench.decode", 0.25)]))
    res, err = tiny.run_cell(root, "tiny-chat", 2**33 + 1, trace=1)
    assert res["correct"], err
    m = res["metrics"]
    assert m["requests_done"]["value"] == res["attempted"] >= 1
    assert m["idle_share.serve"]["value"] == 50.0
    assert set(m) >= {"prefill_ms", "decode_step_ms", "requests_done"}
    # no peaks on a CPU: no share of a peak is made up
    assert "mfu" not in m and "head_roofline" not in m
    assert res["breakdown"]["idle_gaps"] == [["bench.decode", 0.25]]
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("[check] ")


def test_run_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench" / "run.py"),
                        "--workload", "yi6b-chat-decode", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert "correct" not in p.stdout


def test_too_few_chips(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr("jax.devices", lambda: [Dev()])
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.check_devices(4)
    assert harness.check_devices(1)[0].device_kind == "TPU v5 lite"


def test_checks_decide_correct():
    cell = harness.resolve(REPO, BENCH, "yi6b-chat-decode")
    run = harness.Run(cell=cell, seed=1, seconds=1.0)
    assert not run.correct               # nothing compared is not correct
    run.checks = [("logit_gap", 0.0, 1.0)]
    assert run.correct
    run.checks.append(("x", math.nan, 1.0))
    assert not run.correct
