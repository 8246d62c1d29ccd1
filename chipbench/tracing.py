"""Reduction of a profiler trace to the numbers the metric readers take.

A trace is read into plain records (``Plane`` -> ``Line`` -> ``Event``), so
the reduction runs the same on a recorded ``.xplane.pb`` and on a small trace
built by hand in a test.  On a TPU the device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation that ran on the chip.
An operation's event is named by its HLO text; it is kept under its short
name (``net_sweep_pallas.1``).  Host spans are the ``TraceAnnotation``
regions on the ``/host:CPU`` plane.  The TPU's clock in the trace runs up to
about a millisecond apart from the host's, so a gap's host span is a guide,
not a measurement.

* busy time of a device: the length of the union of its operation intervals
  inside the window;
* kernel time: the summed durations of operations whose name matches a
  pattern;
* idle gaps: the stretches of the window with no operation running, each
  attributed to the innermost host span open at its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no host span)"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def op_name(hlo: str) -> str:
    """The short name of a device operation event (``%fusion.2 = f32[...] ...`` -> ``fusion.2``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read_xplane(directory: str, host_names=frozenset()) -> List[Plane]:
    """The newest ``.xplane.pb`` under a profiler log directory, as plain records.

    Only what :func:`reduce` reads is kept: the ``XLA Ops`` line of each
    device plane, and host events named ``bench.*`` or in ``host_names``.
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for p in pd.planes:
        lines = []
        if DEVICE_PLANE.match(p.name):
            for l in p.lines:
                if l.name == OPS_LINE:
                    lines.append(Line(l.name, [Event(op_name(e.name), float(e.start_ns),
                                                     float(e.duration_ns)) for e in l.events]))
        elif p.name == HOST_PLANE:
            for l in p.lines:
                evs = []
                for e in l.events:
                    name = e.name
                    if name.startswith("bench.") or name in host_names:
                        evs.append(Event(name, float(e.start_ns), float(e.duration_ns)))
                lines.append(Line(l.name, evs))
        planes.append(Plane(p.name, lines))
    return planes


def union_length(intervals: List[Tuple[float, float]], t0: float, t1: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to ``[t0, t1]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Tuple[float, float]], t0: float, t1: float) -> List[Tuple[float, float]]:
    """The stretches of ``[t0, t1]`` that no interval covers."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class DeviceTrace:
    """One traced window: per-device operations and the host spans."""

    t0_ns: float
    t1_ns: float
    ops: Dict[str, List[Event]]                   # device plane -> operations
    host: List[Event]                             # host annotations

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        per = [
            union_length([(e.start_ns, e.end_ns) for e in evs], self.t0_ns, self.t1_ns)
            for evs in self.ops.values() if evs
        ]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def kernel(self, pattern: str) -> Tuple[float, int]:
        """(summed seconds over all devices, event count) of operations matching ``pattern``."""
        rx = re.compile(pattern)
        total, n = 0.0, 0
        for evs in self.ops.values():
            for e in evs:
                if rx.search(e.name) and self.t0_ns <= e.start_ns < self.t1_ns:
                    total += e.dur_ns
                    n += 1
        return total / 1e9, n

    def top_ops(self, n: int = 10) -> List[list]:
        """Device operations by summed seconds (over the devices), largest first."""
        tot: Dict[str, float] = {}
        for evs in self.ops.values():
            for e in evs:
                if self.t0_ns <= e.start_ns < self.t1_ns:
                    tot[e.name] = tot.get(e.name, 0.0) + e.dur_ns / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of each device, summed over devices, by the host span open then."""
        # host spans of one thread nest, so a sweep with a stack finds the
        # innermost span open at each (ascending) gap midpoint
        spans = sorted(self.host, key=lambda e: (e.start_ns, -e.dur_ns))
        out: Dict[str, float] = {}
        for evs in self.ops.values():
            stack: List[Event] = []
            i = 0
            for s, e in gaps([(x.start_ns, x.end_ns) for x in evs], self.t0_ns, self.t1_ns):
                mid = 0.5 * (s + e)
                while i < len(spans) and spans[i].start_ns <= mid:
                    while stack and stack[-1].end_ns <= spans[i].start_ns:
                        stack.pop()
                    stack.append(spans[i])
                    i += 1
                while stack and stack[-1].end_ns < mid:
                    stack.pop()
                name = stack[-1].name if stack else NO_SPAN
                out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out

    def top_idle(self, n: int = 10) -> List[list]:
        idle = self.idle_by_span()
        return [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:n]]


def reduce(planes: List[Plane], host_names: Optional[set] = None) -> DeviceTrace:
    """Cut the trace to the benchmark's window span and sort its events.

    ``host_names``: host annotations to keep for gap attribution (default:
    every name that starts with ``bench.``).
    """
    host: List[Event] = []
    ops: Dict[str, List[Event]] = {}
    window = None
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            for l in p.lines:
                if l.name == OPS_LINE:
                    ops[p.name] = sorted(l.events, key=lambda e: e.start_ns)
        elif p.name == HOST_PLANE:
            for l in p.lines:
                for e in l.events:
                    if e.name == WINDOW_SPAN:
                        window = e
                    elif e.name.startswith("bench.") or (host_names and e.name in host_names):
                        host.append(e)
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    if not ops:
        raise ValueError("trace holds no device operations")
    return DeviceTrace(window.start_ns, window.end_ns, ops, host)
