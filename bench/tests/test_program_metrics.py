"""The readers of the program's own spans, on a made-up record and on a
traced tiny run."""

import json

import pytest

from bench import harness
from bench.tests import tiny
from bench.trace import Reduction
from repro.runtime import telemetry

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
METRICS = ("prefill_compile_ms", "decode_host_ms", "release_gap_p95_ms")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def read(metric):
    cell = harness.resolve(REPO, BENCH, "yi6b-chat-decode")
    return harness.reader(cell, metric)(None)


@pytest.fixture
def record():
    """The process tracer, empty before and after the test."""
    tr = telemetry.profile_tracer()
    tr.drain()
    yield tr
    tr.drain()


def made_up(tr):
    # request 0: a prefill at 10 s holding three stages, two of them
    # nested ([10.1, 10.3] u [10.2, 10.7] u [10.3, 10.4] is 0.6 s long),
    # and a stage inside its decode, which the prefill does not count
    tr.emit(telemetry.SERVE_PREFILL, 10.0, 1.0, job=0)
    tr.emit(telemetry.COMPILE, 10.1, 0.2, job=0, label=TRACE)
    tr.emit(telemetry.COMPILE, 10.2, 0.5, job=0, label=LOWER)
    tr.emit(telemetry.COMPILE, 10.3, 0.1, job=0, label=TRACE)
    tr.emit(telemetry.SERVE_DECODE, 11.0, 1.0, job=0)
    tr.emit(telemetry.COMPILE, 11.5, 0.3, job=0, round=1, label=BACKEND)
    for i, (t, cpu) in enumerate([(11.0, 0.001), (11.02, 0.002),
                                  (11.05, 0.003)]):
        tr.emit(telemetry.STEP, t, 0.018, job=0, round=i, value=cpu)
        tr.emit(telemetry.RELEASE, t + 0.01, job=0, round=i, value=2)
    # request 1: one stage of 0.1 s; releases 10 and 40 ms apart,
    # stamped out of step order; a release that is no step's
    tr.emit(telemetry.SERVE_PREFILL, 20.0, 0.5, job=1)
    tr.emit(telemetry.COMPILE, 20.1, 0.1, job=1, label=BACKEND)
    tr.emit(telemetry.STEP, 21.0, 0.018, job=1, round=0, value=0.004)
    for i, t in [(2, 21.06), (0, 21.01), (1, 21.02)]:
        tr.emit(telemetry.RELEASE, t, job=1, round=i, value=2)
    tr.emit(telemetry.RELEASE, 22.0, job=1, value=0)


def test_readers_on_a_made_up_record(record):
    made_up(record)
    # (0.6 + 0.1) s over two prefills
    assert read("prefill_compile_ms") == pytest.approx(350.0)
    # steps of 18 ms, of which the host worked 1, 2, 3 and 4 ms
    assert read("decode_host_ms") == pytest.approx(2.5)
    # gaps 20, 30 (request 0) and 10, 40 ms (request 1): the 95th
    # percentile lies 0.85 of the way from 30 to 40
    assert read("release_gap_p95_ms") == pytest.approx(38.5)


@pytest.mark.parametrize("metric", METRICS)
def test_reader_reads_nothing_on_an_empty_record(record, metric):
    assert record.events() == []
    assert read(metric) is None


def test_traced_tiny_run_reports_them(tmp_path, monkeypatch, record):
    """A ``--trace 1`` run profiles its window: the program's spans land
    in the process tracer, and the result line carries the three."""
    root = tiny.make_checkout(tmp_path)
    # a CPU run has no device trace to reduce
    monkeypatch.setattr(
        "bench.window.Window.reduce", lambda self: Reduction(
            window_s=1.0, busy_s=0.5, busy_by_device={"d": 0.5},
            module_s={}, op_s={}, gaps=[]))
    res, err = tiny.run_cell(root, "tiny-chat", 2**33 + 5, trace=1)
    assert res["correct"], err
    m = res["metrics"]
    assert set(METRICS) <= set(m)
    assert m["prefill_compile_ms"]["value"] > 0
    assert m["decode_host_ms"]["value"] > 0
    assert m["release_gap_p95_ms"]["value"] > 0
    # every request of the window, and none of set-up or the check
    prefills = [e for e in record.events()
                if e.kind == telemetry.SERVE_PREFILL]
    assert len(prefills) == res["attempted"]
