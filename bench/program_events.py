"""What the program's own telemetry recorded in the measured window.

While a profile is taken, ``repro.runtime.telemetry.span`` records the
serve path's spans and releases into the process tracer.  A ``--trace
1`` run profiles only the window, so the tracer then holds the window's
requests alone.  A program without that tracer gives no events, and
the metrics that read them read nothing.
"""


def events() -> list:
    """The process tracer's events, time-sorted; ``[]`` if none."""
    try:
        from repro.runtime.telemetry import profile_tracer
    except ImportError:
        return []
    return profile_tracer().events()
