"""Run one cell: set up, measure one window, check the outputs, print the result.

Everything a cell is made of is found by the names in ``BENCHMARK.json``:

* ``chipbench/configs/<config>.json``: the deployment (tenants, ``n_bits``,
  chips, guarantees);
* ``chipbench/traffic/<mix>.json``: the mix's parameters; its ``mode`` names
  the entry the window drives, ``chipbench/modes/<mode>.py``;
* ``chipbench/metrics/<metric>.py``: one reader per metric, ``read(rec)``
  returning a number or ``None`` when it finds nothing to read (a mode may
  set further attributes on the record for its own readers);
* ``chipbench/networks/<tenant>.json``: each tenant's network, read by the
  program's adapter and by the reference alike.

A run prints the split of its set-up and, last on standard error, each number
compared with its limit; the last line of standard output is the result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SCRATCH = ROOT / ".chipbench"        # traces of --trace 1 runs, deleted after reading


class BenchError(RuntimeError):
    """A cell that cannot run here: unknown name, no chip, a bad file."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` and the files it names, under one checkout root."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.doc = _load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / "chipbench"

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"unknown workload {name!r}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load_json(self.root / c["file"])
        raise BenchError(f"unknown config {name!r}")

    def traffic(self, name: str) -> dict:
        return _load_json(self.dir / "traffic" / f"{name}.json")

    def metric(self, name: str):
        path = self.dir / "metrics" / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"no reader {path}")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics_for(self, cell: str, traced: bool) -> List[dict]:
        """The cell's end-to-end metrics (untraced run) or per-layer metrics (traced)."""
        e2e = [m for m in self.doc["end_to_end"] if cell in m.get("workloads", [cell])]
        if not traced:
            return e2e
        reported = {m["name"] for m in e2e}
        return [
            m for m in self.doc["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in reported)
        ]


def load_mode(name: str):
    module = f"chipbench.modes.{name}"
    try:
        return importlib.import_module(module).Mode
    except ModuleNotFoundError as exc:
        if exc.name != module:
            raise                     # the mode exists; something it imports does not
        raise BenchError(f"unknown traffic mode {name!r}") from exc


class CompileLog:
    """``jax.monitoring`` compile-duration events with the host time they ended."""

    PREFIX = "/jax/core/compile/"

    def __init__(self):
        self.events: List[tuple] = []

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def remove(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, name, dur, **_):
        if name.startswith(self.PREFIX):
            self.events.append((name, float(dur), time.perf_counter()))

    def seconds(self, t0: float, t1: float) -> float:
        """Wall seconds covered by compile events that ended in ``[t0, t1)``
        (the events nest, so this is the union of their intervals)."""
        from chipbench.tracing import union_length

        return union_length([(t - d, t) for _, d, t in self.events if t0 <= t < t1],
                            float("-inf"), float("inf"))


@dataclasses.dataclass
class RunRecord:
    """What one run measured; the metric readers take their numbers from here."""

    cell: dict
    config: dict
    traffic: dict
    seconds: float
    traced: bool
    setup_s: float = float("nan")
    setup_split: Dict[str, float] = dataclasses.field(default_factory=dict)
    t_window: float = float("nan")
    t_end: float = float("nan")
    decisions: int = 0               # decisions returned to the host in the window
    attempted: int = 0
    failed: int = 0
    bench_spans: List[tuple] = dataclasses.field(default_factory=list)  # (name, t0, t1)
    launch_bits: float = 0.0         # sum of frames x n_bits x nodes over the window's launches
    compile_setup_s: float = float("nan")
    device_trace: object = None      # tracing.DeviceTrace of a traced run

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_window


class Context:
    """What a mode needs: the cell's files, the seed, the reference networks."""

    def __init__(self, cell, config, traffic, seed, seconds, traced, control, rec,
                 require_tpu=True):
        from chipbench.reference import Network

        self.cell, self.config, self.traffic = cell, config, traffic
        self.require_tpu = require_tpu
        self.seed = int(seed) % (1 << 64)
        self.seconds = float(seconds)
        self.traced = traced
        self.control = control
        self.rec = rec
        self.n_bits = int(config["n_bits"])
        # the control runs the program on a stream a quarter as long; the
        # check keeps holding it to the stated n_bits
        self.program_n_bits = self.n_bits // 4 if control == "quarter-bits" else self.n_bits
        self.chips = int(cell["chips"])
        self.tenants = list(config["tenants"])
        self.nets = {t: Network.load(t) for t in self.tenants}
        self.docs = {t: _load_json(HERE / "networks" / f"{t}.json") for t in self.tenants}

    def check_kernel(self, fn, *args) -> None:
        """The program ``fn`` lowers to holds the Pallas TPU kernel."""
        import jax

        if "tpu_custom_call" not in jax.jit(fn).lower(*args).as_text():
            raise BenchError("the decide program holds no Pallas TPU kernel")

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark span around a call into a layer (recorded in traced runs)."""
        if not self.traced:
            yield
            return
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(name):
            yield
        self.rec.bench_spans.append((name, t0, time.perf_counter()))


def check_device(chips: int) -> dict:
    """The device as JAX reports it; raises unless it is a TPU that compiles kernels."""
    import jax

    from chipbench.program import backend

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"JAX sees no TPU (platform {d.platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if backend.default_interpret():
        raise BenchError("Pallas kernels would run interpreted")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def host_device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-function Python events
    opts.host_tracer_level = 1     # TraceAnnotation spans and the runtime's own
    return opts


def run_cell(bench: Bench, cell: dict, seed: int, seconds: float, traced: bool, *,
             t0: float, control: Optional[str] = None, require_tpu: bool = True,
             limits: Optional[dict] = None, compile_cache: bool = True,
             out=sys.stdout, err=sys.stderr) -> dict:
    """One run of one cell; returns the result object (also printed last on ``out``)."""
    from chipbench import check, tracing

    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    metric_defs = bench.metrics_for(cell["name"], traced)
    readers = {m["name"]: bench.metric(m["name"]) for m in metric_defs}
    Mode = load_mode(traffic["mode"])

    import jax  # noqa: F401  (after the files are found: a bad name costs no start-up)

    from chipbench.program import backend

    if compile_cache:
        backend.enable_compile_cache()
    clog = CompileLog()
    clog.install()
    device = check_device(int(cell["chips"])) if require_tpu else host_device()
    rec = RunRecord(cell, config, traffic, float(seconds), traced)
    ctx = Context(cell, config, traffic, seed, seconds, traced, control, rec, require_tpu)
    t_init = time.perf_counter()
    mode = Mode(ctx)
    rec.setup_split = {"init_s": t_init - t0, **mode.setup()}
    trace_dir = None
    if traced:
        import jax.profiler

        trace_dir = SCRATCH / f"trace-{cell['name']}-{ctx.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=_profiler_options())
    rec.t_window = time.perf_counter()
    rec.setup_s = rec.t_window - t0
    rec.compile_setup_s = clog.seconds(t0, rec.t_window)
    try:
        if traced:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                mode.window()
        else:
            mode.window()
    finally:
        clog.remove()
        if traced:
            jax.profiler.stop_trace()
    device["memory_peak_bytes"] = memory_peak(int(cell["chips"]))
    if traced:
        planes = tracing.read_xplane(str(trace_dir), mode.host_span_names)
        rec.device_trace = tracing.reduce(planes, mode.host_span_names)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = rec.device_trace.busy_s()
        device["window_s"] = rec.device_trace.window_s
    comparison = check.Comparison(ctx.n_bits)
    mode.compare(comparison)   # frees the program's state, then runs the reference
    gc.collect()
    readings = comparison.readings()
    correct, shown = check.judge(readings, limits if limits is not None else check.load_limits())

    metrics = {}
    for m in metric_defs:
        v = readers[m["name"]].read(rec)
        if v is None:
            if not traced:
                raise BenchError(f"end-to-end metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": int(rec.attempted),
        "failed": int(rec.failed),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = {
            "device_ops": rec.device_trace.top_ops(10),
            "idle_gaps": rec.device_trace.top_idle(10),
        }
    result["check"] = shown
    split = ", ".join(f"{k} {v:.3f}" for k, v in rec.setup_split.items())
    print(f"setup_s {rec.setup_s:.3f}: {split}; compile events {rec.compile_setup_s:.3f} s; "
          f"window {rec.window_s:.3f} s", file=err)
    for name, v in mode.notes().items():
        print(f"{name}: {v}", file=err)
    print(f"posterior_tie_flips (shown, not judged): {comparison.tie_flips}, "
          f"at exact count ties: {comparison.tie_flips_at_ties}", file=err)
    for name, s in shown.items():
        print(f"check {name} {s['value']} limit {s['limit']}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("quarter-bits",), default=None,
                    help="run the program on a quarter of the stated n_bits "
                         "(the control of the output check; never in a benchmark run)")
    args = ap.parse_args(argv)
    try:
        bench = Bench()
        cell = bench.cell(args.workload)
        run_cell(bench, cell, args.seed, args.seconds, bool(args.trace), t0=t0,
                 control=args.control)
    except BenchError as exc:
        print(f"FAILED: {exc}", file=sys.stderr, flush=True)
        return 2
    return 0
