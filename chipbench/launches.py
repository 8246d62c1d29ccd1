#!/usr/bin/env python3
"""Each decide launch of a traced replay window, split on the profiler's clock.

A program compiled with ``trace=Tracer(annotate=True)`` writes its
``decide`` / ``decide.evidence`` / ``decide.dispatch`` spans into the
profiler's trace, on the host's clock; each device's ``XLA Modules`` line holds
one event per execution of the decide program.  The window's k-th ``decide``
span is matched to the k-th execution on each device and to the k-th
``bench.fetch`` span, which returned it (the replay mode fetches in launch
order).  A launch's first and last operations are the ``XLA Ops`` events inside
its execution.

**Clock.**  A device's timestamps are offset from the host's by an unknown
``d`` (device = host + d).  Causality brackets it: a launch's first operation
starts after its ``decide.dispatch`` began, and its last operation ends before
the ``bench.fetch`` that returned it ended, so
``d`` lies in ``[max_k(last_op_end - fetch_end), min_k(first_op_start - dispatch_start)]``.
Device times are corrected by the bracket's midpoint.  An empty bracket means
the matching is wrong: nothing is read, and the reason is given.

Readings, over the window's launches (pooled over devices):

* ``decide.host_us_per_launch``: mean duration of the ``decide`` spans;
* ``launch.to_device_us_p50``: median of first op start - max(end of its
  ``decide`` span, the previous launch's last op end);
* ``launch.device_us_p50``: median of last op end - first op start;
* ``launch.return_us_p50``: median of end of its fetch - max(start of that
  fetch, its last op end);

with the means of each beside them.  Run on the chip, in one process:

    python3 chipbench/launches.py --workload fleet-edge.replay --seed <n> --seconds 20

It warms the cell's programs as a benchmark run does, then times windows of
the untraced programs and of their traced twins (the same compiled programs,
profiler off) in turn, then profiles one window of the traced programs and
prints its split.  The last line of standard output is a JSON object.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import re
import shutil
import statistics
import sys
import time
from typing import Dict, List, Tuple

if __name__ == "__main__":
    T0 = time.perf_counter()
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench.tracing import (  # noqa: E402
    DEVICE_PLANE, HOST_PLANE, OPS_LINE, WINDOW_SPAN, Event, Line, Plane, op_name,
)

SPANS = ("decide", "decide.evidence", "decide.dispatch")
FETCH = "bench.fetch"
MODULES_LINE = "XLA Modules"
PROGRAM = re.compile(r"_decide")   # the decide program's module name (jit__decide)


class MatchError(ValueError):
    """The trace's launches cannot be matched to its host spans."""


def read_xplane(directory: str) -> List[Plane]:
    """The newest ``.xplane.pb`` under a profiler log directory, as plain records.

    Keeps each device plane's ``XLA Ops`` and ``XLA Modules`` lines, and the
    host events named ``bench.*`` or in :data:`SPANS`
    (:func:`chipbench.tracing.read_xplane` keeps no ``XLA Modules`` line).
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    planes = []
    for p in ProfileData.from_file(paths[-1]).planes:
        if DEVICE_PLANE.match(p.name):
            lines = [Line(l.name, [Event(op_name(e.name), float(e.start_ns), float(e.duration_ns))
                                   for e in l.events])
                     for l in p.lines if l.name in (OPS_LINE, MODULES_LINE)]
        elif p.name == HOST_PLANE:
            lines = [Line(l.name, [Event(e.name, float(e.start_ns), float(e.duration_ns))
                                   for e in l.events
                                   if e.name.startswith("bench.") or e.name in SPANS])
                     for l in p.lines]
        else:
            continue
        planes.append(Plane(p.name, lines))
    return planes


@dataclasses.dataclass
class Launches:
    """The window's launches: host spans in launch order, executions per device."""

    t0_ns: float
    t1_ns: float
    spans: Dict[str, List[Event]]                       # SPANS and FETCH -> events
    execs: Dict[str, List[Tuple[float, float]]]         # device -> (first op start, last op end)

    @property
    def n(self) -> int:
        return len(self.spans["decide"])


def _line(plane: Plane, name: str) -> List[Event]:
    return sorted((e for l in plane.lines if l.name == name for e in l.events),
                  key=lambda e: e.start_ns)


def match(planes: List[Plane], program=PROGRAM) -> Launches:
    """Pair each ``decide`` span of the window with its execution on every device."""
    host = [e for p in planes if p.name == HOST_PLANE for l in p.lines for e in l.events]
    window = next((e for e in host if e.name == WINDOW_SPAN), None)
    if window is None:
        raise MatchError(f"trace holds no {WINDOW_SPAN!r} span")
    spans = {
        name: sorted((e for e in host
                      if e.name == name and window.start_ns <= e.start_ns < window.end_ns),
                     key=lambda e: e.start_ns)
        for name in SPANS + (FETCH,)
    }
    n = len(spans["decide"])
    if not n:
        raise MatchError("the window holds no decide span: was the program traced?")
    for name, evs in spans.items():
        if len(evs) != n:
            raise MatchError(f"{len(evs)} {name} spans for {n} decide spans")
    execs = {}
    for p in planes:
        if not DEVICE_PLANE.match(p.name):
            continue
        every = _line(p, MODULES_LINE)
        modules = [m for m in every if program.search(m.name)]
        if len(modules) != n:
            seen = sorted({m.name for m in every})[:5]
            raise MatchError(f"{p.name}: {len(modules)} executions of the decide program "
                             f"for {n} decide spans (modules seen: {seen})")
        ops, i, out = _line(p, OPS_LINE), 0, []
        for m in modules:
            while i < len(ops) and ops[i].start_ns < m.start_ns:
                i += 1
            inside = []
            while i < len(ops) and ops[i].start_ns <= m.end_ns:
                inside.append(ops[i])
                i += 1
            out.append((inside[0].start_ns, max(e.end_ns for e in inside)) if inside
                       else (m.start_ns, m.end_ns))
        execs[p.name] = out
    if not execs:
        raise MatchError("trace holds no device plane")
    return Launches(window.start_ns, window.end_ns, spans, execs)


def skew_bracket(lt: Launches, device: str) -> Tuple[float, float]:
    """``(lo, hi)`` ns: the device clock's offset from the host's that causality allows."""
    ex = lt.execs[device]
    lo = max(b - f.end_ns for (_, b), f in zip(ex, lt.spans[FETCH]))
    hi = min(a - d.start_ns for (a, _), d in zip(ex, lt.spans["decide.dispatch"]))
    return lo, hi


def offsets(lt: Launches, at: float = 0.5) -> Dict[str, float]:
    """Each device's clock offset, ``lo + at (hi - lo)`` of its bracket (the
    midpoint by default); raises on an empty bracket."""
    out = {}
    for dev in lt.execs:
        lo, hi = skew_bracket(lt, dev)
        if lo > hi:
            raise MatchError(f"{dev}: empty clock-skew bracket [{lo / 1e3:.3f}, {hi / 1e3:.3f}] us "
                             "(a launch ends after its fetch or starts before its dispatch)")
        out[dev] = lo + at * (hi - lo)
    return out


def split(lt: Launches, at: float = 0.5) -> Dict[str, float]:
    """The readings of the module docstring, in microseconds, with the device
    clocks corrected by :func:`offsets` ``(lt, at)``."""
    delta = offsets(lt, at)
    decide, fetch = lt.spans["decide"], lt.spans[FETCH]
    to_dev, dev_us, ret = [], [], []
    for name, ex in lt.execs.items():
        prev_end = float("-inf")
        for (a, b), d, f in zip(ex, decide, fetch):
            a, b = a - delta[name], b - delta[name]
            to_dev.append(a - max(d.end_ns, prev_end))
            dev_us.append(b - a)
            ret.append(f.end_ns - max(f.start_ns, b))
            prev_end = b
    out = {
        "launches": lt.n,
        "window_us_per_launch": (lt.t1_ns - lt.t0_ns) / lt.n / 1e3,
        "decide.host_us_per_launch": statistics.fmean(e.dur_ns for e in decide) / 1e3,
    }
    for name in ("decide.evidence", "decide.dispatch", FETCH):
        out[f"{name}_us_mean"] = statistics.fmean(e.dur_ns for e in lt.spans[name]) / 1e3
    for key, vals in (("to_device", to_dev), ("device", dev_us), ("return", ret)):
        out[f"launch.{key}_us_p50"] = statistics.median(vals) / 1e3
        out[f"launch.{key}_us_mean"] = statistics.fmean(vals) / 1e3
    return out


def on_host_clock(planes: List[Plane], delta: Dict[str, float]) -> List[Plane]:
    """The planes with each device's events moved onto the host's clock."""
    return [
        Plane(p.name, [Line(l.name, [Event(e.name, e.start_ns - delta[p.name], e.dur_ns)
                                     for e in l.events]) for l in p.lines])
        if p.name in delta else p
        for p in planes
    ]


def _window_rate(mode, rec) -> float:
    mode.window()
    return rec.decisions / rec.window_s


def measure(bench, cell: dict, seed: int, seconds: float, cost_seconds: float,
            cost_rounds: int, *, t0: float, require_tpu: bool = True,
            compile_cache: bool = True, err=sys.stderr) -> dict:
    """Set the cell up, compare untraced and traced windows, split a profiled one."""
    import jax.profiler

    from chipbench import harness, tracing
    from chipbench.program import backend
    from repro.obs import Tracer

    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    Mode = harness.load_mode(traffic["mode"])
    if compile_cache:
        backend.enable_compile_cache()
    chips = int(cell["chips"])
    device = harness.check_device(chips) if require_tpu else harness.host_device()
    rec = harness.RunRecord(cell, config, traffic, cost_seconds, False)
    ctx = harness.Context(cell, config, traffic, seed, cost_seconds, False, None, rec,
                          require_tpu)
    mode = Mode(ctx)
    mode.setup()
    setup_s = time.perf_counter() - t0
    tracer = Tracer(annotate=True)
    plain = mode.nets
    traced = {t: dataclasses.replace(net, trace=tracer) for t, net in plain.items()}

    rates = {"untraced": [], "traced": []}
    sides = [("untraced", plain), ("traced", traced)]
    for _ in range(cost_rounds):
        for name, nets in sides:
            mode.nets = nets
            rates[name].append(_window_rate(mode, rec))
        sides.reverse()   # each side goes first in every other round

    ctx.seconds, ctx.traced = seconds, True   # bench.* spans from here on
    mode.nets = traced
    trace_dir = harness.SCRATCH / f"launches-{cell['name']}-{ctx.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = tracer.count("decide.compiles")
    jax.profiler.start_trace(str(trace_dir), profiler_options=harness._profiler_options())
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            profiled_rate = _window_rate(mode, rec)
    finally:
        jax.profiler.stop_trace()
    profiled = {"decisions_per_s": profiled_rate,
                "decide.window_compiles": tracer.count("decide.compiles") - compiles}
    planes = read_xplane(str(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        lt = match(planes)
        delta = offsets(lt)
        for dev in lt.execs:
            lo, hi = skew_bracket(lt, dev)
            print(f"clock skew us [{lo / 1e3:.3f}, {hi / 1e3:.3f}] {dev}", file=err)
        profiled.update(split(lt))
        profiled["skew_us"] = {d: v / 1e3 for d, v in delta.items()}
        # how far the readings move across the bracket
        for at in (0.0, 1.0):
            edge = split(lt, at)
            profiled[f"at_bracket_{at:g}"] = {
                k: edge[k] for k in ("launch.to_device_us_p50", "launch.return_us_p50")}
        planes = on_host_clock(planes, delta)
    except MatchError as exc:
        print(f"launch split: nothing read: {exc}", file=err)
    try:
        dt = tracing.reduce(planes, set(SPANS))
        profiled.update(idle_pct=100.0 * (1.0 - dt.busy_s() / dt.window_s),
                        idle_gaps=dt.top_idle(10), device_ops=dt.top_ops(10))
    except ValueError as exc:   # no device operations in the trace
        print(f"device trace: nothing read: {exc}", file=err)
    return {
        "device": device,
        "setup_s": setup_s,
        "decisions_per_s": {
            **rates,
            "traced_over_untraced": [t / u for u, t in zip(rates["untraced"], rates["traced"])],
        },
        "profiled": profiled,
    }


def main(argv=None, t0=None) -> int:
    from chipbench import harness

    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Split each decide launch of a traced window.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0, help="the profiled window")
    ap.add_argument("--cost-seconds", type=float, default=5.0,
                    help="each window of the untraced / traced comparison (profiler off)")
    ap.add_argument("--cost-rounds", type=int, default=3)
    args = ap.parse_args(argv)
    try:
        bench = harness.Bench()
        result = measure(bench, bench.cell(args.workload), args.seed, args.seconds,
                         args.cost_seconds, args.cost_rounds, t0=t0)
    except harness.BenchError as exc:
        print(f"FAILED: {exc}", file=sys.stderr, flush=True)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0))
