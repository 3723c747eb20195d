"""Reduce a profiler trace to device busy time, program time and gaps.

A ``--trace 1`` run records the measured window with ``jax.profiler``.
The harness wraps the window in a host span ``bench.window`` and each
call into the program in a span of its own (``bench.prefill``,
``bench.decode``), so everything below is read on the trace's own
clock:

* busy time of each device: the union of its operations' intervals
  inside the window; the idle share is 1 - busy / window;
* device time of each XLA program (module), summed over the devices;
* the device operations that took most time, and the longest idle gaps,
  each gap named by the innermost ``bench.`` span open at its middle.

``load`` reads an ``.xplane.pb`` file into plain :class:`Event` records,
keeping only what the reduction reads; ``reduce`` works on those records
alone, so a test can hand it a made-up trace.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

__all__ = ["Event", "Reduction", "load", "reduce", "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: device-plane lines holding one event per operation / per program run
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    kind: str          # "op" | "module" (device), "span" (host)
    device: str        # device plane name; "" for a host span
    name: str
    start: float       # nanoseconds, on the trace's clock
    dur: float


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                  # mean over devices
    busy_by_device: dict
    module_s: dict                 # program name -> device seconds, summed
    op_s: dict                     # operation name -> device seconds
    gaps: list                     # [(span name, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def load(path: str) -> list[Event]:
    """The events of one ``.xplane.pb`` that :func:`reduce` reads."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                kind = "op" if line.name == OPS_LINE else "module"
                out.extend(Event(kind, plane.name, e.name, e.start_ns,
                                 e.duration_ns) for e in line.events)
            elif not device and plane.name.startswith("/host:"):
                out.extend(Event("span", "", e.name, e.start_ns,
                                 e.duration_ns) for e in line.events
                           if e.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(e: Event, lo: float, hi: float) -> Optional[tuple[float, float]]:
    a, b = max(e.start, lo), min(e.start + e.dur, hi)
    return (a, b) if b > a else None


def _label(spans: list[Event], t: float) -> str:
    """The innermost harness span open at ``t`` (the one started last)."""
    best = None
    for s in spans:
        if s.start <= t <= s.start + s.dur and s.name != WINDOW_SPAN:
            if best is None or s.start > best.start:
                best = s
    return best.name if best is not None else "none"


def reduce(events: list[Event]) -> Reduction:
    """Busy, program and gap figures inside the ``bench.window`` span.

    Raises ``ValueError`` when the trace has no window span or no device
    operation: such a trace measured nothing.
    """
    windows = [e for e in events if e.kind == "span"
               and e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"want one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].start + windows[0].dur
    spans = [e for e in events if e.kind == "span"]
    devices = sorted({e.device for e in events if e.kind == "op"})
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy, gaps = {}, []
    op_ns: dict[str, float] = {}
    mod_ns: dict[str, float] = {}
    for dev in devices:
        iv = []
        for e in events:
            if e.device != dev:
                continue
            c = _clip(e, lo, hi)
            if c is None:
                continue
            if e.kind == "op":
                iv.append(c)
                op_ns[e.name] = op_ns.get(e.name, 0.0) + c[1] - c[0]
            elif e.kind == "module":
                name = _SUFFIX.sub("", e.name)
                mod_ns[name] = mod_ns.get(name, 0.0) + c[1] - c[0]
        u = _union(iv)
        busy[dev] = sum(b - a for a, b in u) / 1e9
        edges = [lo] + [t for ab in u for t in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(spans, (a + b) / 2), (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy.values()) / len(busy), busy_by_device=busy,
        module_s={k: v / 1e9 for k, v in mod_ns.items()},
        op_s={k: v / 1e9 for k, v in op_ns.items()}, gaps=gaps)
