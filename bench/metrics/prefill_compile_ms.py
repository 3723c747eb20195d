"""JAX tracing, lowering and compiling per ``ProgressiveServer.prefill``
call (program span, progressive server).

``repro.runtime.telemetry`` records, while the window is profiled, a
``serve_prefill`` span per call and a ``compile`` span per stage of
JAX's tracing, lowering, backend compile and compile-cache read inside
it.  The stages nest (the cache read lies inside the backend compile),
so a call counts the length of the union of its stages' intervals.  The
reading is that length summed over the calls and divided by their
number, in ms.  A program that records no ``serve_prefill`` reads
nothing.
"""

from bench import program_events


def read(run):
    del run
    events = program_events.events()
    prefills = [e for e in events if e.kind == "serve_prefill"]
    if not prefills:
        return None
    total = 0.0
    for p in prefills:
        stages = sorted((c.t, c.t + c.dur) for c in events
                        if c.kind == "compile" and c.job == p.job
                        and p.t <= c.t + c.dur / 2 <= p.t + p.dur)
        end = -float("inf")
        for a, b in stages:
            total += max(0.0, b - max(a, end))
            end = max(end, b)
    return 1e3 * total / len(prefills)
