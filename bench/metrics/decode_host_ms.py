"""Host CPU time to dispatch one decode step of ``ProgressiveServer.decode``,
in ms (program span, progressive server).

``repro.runtime.telemetry`` records, while the window is profiled, a
``step`` span per decode step whose ``value`` is the serving thread's CPU
seconds inside it.  The span's own length is no measure of the host:
once the runtime's queue of programs is full, each dispatch blocks until
the device frees a place, so a step's span lasts as long as the device's
step.  The CPU time leaves that wait out.  The reading is its mean.
"""

from bench import program_events


def read(run):
    del run
    cpu = [e.value for e in program_events.events() if e.kind == "step"]
    return 1e3 * sum(cpu) / len(cpu) if cpu else None
