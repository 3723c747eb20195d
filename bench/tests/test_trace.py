"""The trace reduction on a made-up trace, and the loader on a live one."""

import glob

import pytest

from bench import trace as T

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def made_up():
    op = lambda dev, name, a, b: T.Event("op", dev, name, a, b - a)
    return [
        T.Event("span", "", "bench.window", 1000, 10000),
        T.Event("span", "", "bench.decode", 1000, 4200),
        T.Event("span", "", "bench.prefill", 5500, 3500),
        op(D0, "A", 1000, 3000), op(D0, "B", 2000, 4000),
        op(D0, "A", 6000, 7000), op(D0, "C", 10500, 12000),
        T.Event("module", D0, "jit_f(12)", 1000, 3000),
        T.Event("module", D0, "jit_g(3)", 6000, 1000),
        op(D1, "A", 0, 11000),
    ]


def test_made_up_trace():
    r = T.reduce(made_up())
    assert r.window_s == pytest.approx(1e-5)
    # device 0: [1000, 4000] + [6000, 7000] + [10500, 11000] inside the
    # window; device 1 busy all through it
    assert r.busy_by_device == {D0: pytest.approx(4.5e-6),
                                D1: pytest.approx(1e-5)}
    assert r.busy_s == pytest.approx((4.5e-6 + 1e-5) / 2)
    assert r.idle_share == pytest.approx(1 - 0.725)
    assert r.op_s == {"A": pytest.approx(3e-6 + 1e-5),
                      "B": pytest.approx(2e-6), "C": pytest.approx(5e-7)}
    assert r.module_s == {"jit_f": pytest.approx(3e-6),
                          "jit_g": pytest.approx(1e-6)}
    assert r.gaps == [("bench.prefill", pytest.approx(3.5e-6)),
                      ("bench.decode", pytest.approx(2e-6))]
    b = r.breakdown()
    assert b["device_ops"][0][0] == "A" and len(b["idle_gaps"]) == 2


@pytest.mark.parametrize("drop", ["bench.window", "op"])
def test_trace_that_measured_nothing(drop):
    ev = [e for e in made_up() if drop not in (e.name, e.kind)]
    with pytest.raises(ValueError):
        T.reduce(ev)


def test_loads_spans_from_a_live_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    from bench.window import Window, span
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with Window(tmp_path) as w:
        with span("bench.decode"):
            f(x).block_until_ready()
    assert w.compiles == 0 and w.end > w.start
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = [e for p in files for e in T.load(p)]
    assert {e.name for e in ev if e.kind == "span"} == {"bench.window",
                                                        "bench.decode"}
