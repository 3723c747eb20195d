"""Spans, compile stages and per-step releases of the serve path.

``ProgressiveServer`` records through ``telemetry.span``: into a tracer it
is given, or into the process tracer while a profile is taken, and into
nothing otherwise.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.launch.serve import ProgressiveServer
from repro.models import transformer as T
from repro.runtime import telemetry

P, G = 8, 4


@pytest.fixture(scope="module")
def model():
    cfg = registry.get_smoke_config("llama3-8b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, P)), jnp.int32)
    return cfg, params, prompt


def request(server, prompt, **budget):
    """One prefill and G decode steps; returns the served tokens."""
    logits, caches = server.prefill(prompt, P + G)
    first = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    out, _ = server.decode(first, caches, P, G, **budget)
    return np.asarray(out)


def by_kind(events):
    kinds = {}
    for e in events:
        kinds.setdefault(e.kind, []).append(e)
    return kinds


def check_request(events, job, release_value):
    """One request's events: spans, releases and their order."""
    k = by_kind(e for e in events if e.job == job)
    assert len(k[telemetry.SERVE_PREFILL]) == 1
    assert len(k[telemetry.SERVE_DECODE]) == 1
    for kind in (telemetry.STEP, telemetry.HIDDEN_STEP, telemetry.HEAD):
        assert [e.round for e in k[kind]] == list(range(G)), kind
    steps, rel = k[telemetry.STEP], k[telemetry.RELEASE]
    assert [e.round for e in rel] == list(range(G))      # in step order
    assert [e.t for e in rel] == sorted(e.t for e in rel)
    assert all(r.t >= s.t for r, s in zip(rel, steps))
    assert all(r.value == release_value for r in rel)
    assert all(e.dur == 0.0 for e in rel)
    dec = k[telemetry.SERVE_DECODE][0]
    for s in steps:
        assert dec.t <= s.t and s.t + s.dur <= dec.t + dec.dur
        # the thread's CPU seconds inside the step, within its length
        assert 0 < s.value <= s.dur + 1e-3
    return k


def test_profiled_request_records_into_the_process_tracer(model, tmp_path):
    cfg, params, prompt = model
    server = ProgressiveServer(cfg, params, m=2, d=7)
    telemetry.profile_tracer().drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        request(server, prompt)
    finally:
        jax.profiler.stop_trace()
    events = telemetry.profile_tracer().drain()
    assert {e.job for e in events} == {server._job}
    k = check_request(events, server._job, release_value=2)
    assert {e.kind for e in events} <= telemetry.EVENT_KINDS
    assert len(k[telemetry.STEP]) == len(k[telemetry.RELEASE]) == G
    pre = k[telemetry.SERVE_PREFILL][0]
    inside = [c for c in k[telemetry.COMPILE]
              if pre.t <= c.t and c.t + c.dur <= pre.t + pre.dur + 1e-3]
    assert inside
    assert {c.label for c in inside} <= telemetry.COMPILE_EVENTS
    assert all(c.round == -1 for c in inside)
    # the first decode compiles the step and the head: inside its steps
    assert any(c.round >= 0 for c in k[telemetry.COMPILE])
    # nothing more once the profile has stopped
    request(server, prompt)
    assert telemetry.profile_tracer().events() == []


def test_nothing_recorded_and_no_thread_when_off(model, monkeypatch):
    cfg, params, prompt = model
    server = ProgressiveServer(cfg, params, m=2, d=7)
    started = []
    start = threading.Thread.start

    def counting_start(self):
        started.append(self.name)
        start(self)

    monkeypatch.setattr(threading.Thread, "start", counting_start)
    telemetry.profile_tracer().drain()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert telemetry.active_tracer() is None
    assert type(telemetry.span(telemetry.STEP, "serve.step")) is \
        jax.profiler.TraceAnnotation
    out = request(server, prompt)
    assert out.shape == (2, G)
    assert started == []
    assert telemetry.profile_tracer().events() == []
    assert server._job == 0


@pytest.mark.parametrize("budget", [{}, {"deadline_ms": 1e9}],
                         ids=["on_chip_head", "deadline_head"])
def test_given_tracer_records_without_profiler(model, budget):
    cfg, params, prompt = model
    tr = telemetry.Tracer()
    telemetry.profile_tracer().drain()
    with ProgressiveServer(cfg, params, m=2, d=7, tracer=tr) as server:
        request(server, prompt, **budget)
        request(server, prompt, **budget)
    events = tr.events()
    assert {e.job for e in events} == {0, 1}
    full = 3 if budget else 2          # 2m - 1 layered resolutions, or m
    for job in (0, 1):
        check_request(events, job, release_value=full)
    assert telemetry.profile_tracer().events() == []


def test_programs_keep_their_names(model):
    """The benchmark's trace reduction finds the programs by name: the
    prefill is not the head's ``jit__lambda``."""
    cfg, params, prompt = model
    server = ProgressiveServer(cfg, params, m=2, d=7)
    pre = server._prefill.lower(server.params, prompt, {}, max_len=P + G)
    assert "module @jit_prefill" in pre.as_text()
    _, caches = server.prefill(prompt, P + G)
    tok = prompt[:, -1:]
    step = server.hidden_step.lower(params, tok, caches, jnp.int32(P))
    assert "module @jit_hidden_step" in step.as_text()
    assert 'hidden_step/' in step.as_text(debug_info=True)
    hidden = jnp.zeros((2, cfg.d_model), jnp.float32)
    head = server.head_series.func.lower(server.lm_head, hidden)
    assert "module @jit__lambda" in head.as_text()
    assert "layered_head/" in head.as_text(debug_info=True)


def test_exporters_take_a_serve_tracer(model):
    """A serve tracer's events go through the runtime's exporters."""
    from types import SimpleNamespace

    from repro.runtime import trace_export
    cfg, params, prompt = model
    tr = telemetry.Tracer()
    request(ProgressiveServer(cfg, params, m=2, d=7, tracer=tr), prompt)
    run = SimpleNamespace(trace_events=tr.events(), backend="serve")
    trace = trace_export.chrome_trace(run)["traceEvents"]
    steps = [e for e in trace if e.get("cat") == telemetry.STEP]
    assert len(steps) == G and all(e["ph"] == "X" for e in steps)
    spans = [e for e in trace if e.get("cat") in telemetry.SPAN_KINDS]
    assert len({e["tid"] for e in spans}) == 1     # nested on one track
    releases = [e for e in trace if e.get("cat") == telemetry.RELEASE]
    assert [e["args"]["round"] for e in releases] == list(range(G))
    lines = list(trace_export.jsonl_lines(run))
    assert len(lines) == len(tr.events())
