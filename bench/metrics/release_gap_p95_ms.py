"""95th percentile of the gap between the releases of consecutive decode
steps of one request, in ms (program span, progressive server).

A ``release`` is the time a step's tokens are ready on the device, which
``repro.runtime.telemetry`` stamps per step while the window is profiled
(``job`` the request, ``round`` the step): G - 1 gaps a request.  The
percentile interpolates linearly between the two nearest gaps.
"""

import numpy as np

from bench import program_events


def read(run):
    del run
    by_job = {}
    for e in program_events.events():
        if e.kind == "release" and e.round >= 0:
            by_job.setdefault(e.job, []).append((e.round, e.t))
    gaps = []
    for rel in by_job.values():
        t = [t for _, t in sorted(rel)]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
