"""The measured window: host clock, compile count and, on request, a trace.

Inside the window every new program JAX lowers is counted: there should
be none, since set-up warmed every shape.  With a trace directory the
window is recorded by the profiler (Python tracer off) inside one
``bench.window`` span, which :mod:`bench.trace` reduces.
"""

from __future__ import annotations

import glob
import os
import time

import jax

from bench import trace as T

__all__ = ["Window", "span", "clock"]

clock = time.monotonic
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def span(name: str):
    """A named host span on the profiler's clock (free when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


class Window:
    def __init__(self, trace_dir=None):
        self.trace_dir = None if trace_dir is None else str(trace_dir)
        self.compiles = 0
        self._active = False
        self._span = None
        self.start = self.end = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **kw):
        del secs, kw
        if self._active and name == _LOWERING:
            self.compiles += 1

    def __enter__(self) -> "Window":
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = span(T.WINDOW_SPAN)
        self._span.__enter__()
        self._active = True
        self.start = clock()
        return self

    def close(self) -> None:
        """End the window (idempotent): the work it counts has ended."""
        if self._span is None:
            return
        self.end = clock()
        self._active = False
        self._span.__exit__(None, None, None)
        self._span = None
        if self.trace_dir is not None:
            jax.profiler.stop_trace()

    def __exit__(self, *exc) -> None:
        self.close()

    def reduce(self) -> T.Reduction:
        files = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no profile written under {self.trace_dir}")
        events = [e for f in files for e in T.load(f)]
        return T.reduce(events)
