"""How unevenly routing loads the held experts of a decode step (program
span, expert layer).

``repro.runtime.telemetry`` records, while the window is profiled, an
``expert_pairs`` event per decode step, expert layer and held expert:
the (token, expert) pairs the step routed to that expert, the grouped
product's group size.  For each step and layer, the pairs of the held
expert with the most over the held experts' mean; the reading is the
mean of that ratio over the steps and layers that routed any pair to a
held expert.  1 is an even load; the largest group sets how long the
grouped product takes.  A program or model that records no
``expert_pairs`` reads nothing.
"""

from bench import program_events


def read(run):
    del run
    groups = {}
    for e in program_events.events():
        if e.kind == "expert_pairs":
            groups.setdefault((e.job, e.round, e.task), []).append(e.value)
    ratios = [max(v) * len(v) / sum(v) for v in groups.values() if sum(v)]
    return sum(ratios) / len(ratios) if ratios else None
