"""Launch matching, the clock-skew bracket and the per-launch split, on traces built by hand."""

import dataclasses
import io
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import harness, tracing
from chipbench.launches import (
    FETCH, MatchError, main, match, measure, offsets, on_host_clock, read_xplane, skew_bracket,
    split,
)
from chipbench.tracing import Event, Line, Plane

# Three launches, two in flight, on the host's clock (ns).  Launch 0 starts on
# the device as its dispatch begins and launch 1 ends as its fetch ends, so
# the bracket closes on the true offset.
DECIDE = [(0, 100), (110, 200), (330, 400)]
EVIDENCE = [(10, 40), (120, 140), (340, 360)]
DISPATCH = [(50, 100), (150, 200), (370, 400)]
FETCHES = [(210, 320), (410, 500), (610, 700)]
EXECS = [(50, 300), (310, 500), (520, 600)]     # first op start, last op end
WINDOW = (-10, 800)


def ev(name, start, end):
    return Event(name, float(start), float(end - start))


def host_events(fetches=FETCHES, decide_spans=True):
    out = [ev("bench.window", *WINDOW)]
    for (d, e, p, f) in zip(DECIDE, EVIDENCE, DISPATCH, fetches):
        out.append(ev("bench.dispatch", d[0] - 1, d[1] + 1))
        if decide_spans:
            out += [ev("decide", *d), ev("decide.evidence", *e), ev("decide.dispatch", *p)]
        out.append(ev(FETCH, *f))
    out.append(ev("decide", 900, 950))     # outside the window: not a launch of it
    return out


def device_plane(name, delta, execs=EXECS, modules=True):
    ops, mods = [], []
    for a, b in execs:
        mid = (a + b) // 2
        ops += [ev("net_sweep_pallas.1", a + delta, mid + delta),
                ev("fusion.1", mid + delta, b + delta)]
        mods.append(ev("jit__decide(7)", a - 5 + delta, b + 5 + delta))
    lines = [Line("XLA Ops", ops)]
    if modules:
        lines.insert(0, Line("XLA Modules", mods))
    return Plane(name, lines)


def planes(deltas=(1000,), fetches=FETCHES, decide_spans=True, modules=True):
    return [Plane("/host:CPU", [Line("python", host_events(fetches, decide_spans))])] + [
        device_plane(f"/device:TPU:{i}", d, modules=modules) for i, d in enumerate(deltas)
    ]


EXPECTED = {
    "launches": 3,
    "window_us_per_launch": 810 / 3 / 1e3,
    "decide.host_us_per_launch": (100 + 90 + 70) / 3 / 1e3,
    "decide.evidence_us_mean": (30 + 20 + 20) / 3 / 1e3,
    "decide.dispatch_us_mean": (50 + 50 + 30) / 3 / 1e3,
    "bench.fetch_us_mean": (110 + 90 + 90) / 3 / 1e3,
    # first op start - max(decide end, previous last op end): 50-100, 310-300, 520-500
    "launch.to_device_us_p50": 10 / 1e3,
    "launch.to_device_us_mean": (-50 + 10 + 20) / 3 / 1e3,
    "launch.device_us_p50": 190 / 1e3,
    "launch.device_us_mean": (250 + 190 + 80) / 3 / 1e3,
    # fetch end - max(fetch start, last op end): 320-300, 500-500, 700-610
    "launch.return_us_p50": 20 / 1e3,
    "launch.return_us_mean": (20 + 0 + 90) / 3 / 1e3,
}


def test_one_device_is_matched_launch_by_launch():
    lt = match(planes())
    assert lt.n == 3 and (lt.t0_ns, lt.t1_ns) == WINDOW
    assert list(lt.execs) == ["/device:TPU:0"]
    # the executions' bounds are their first and last operations, not the module's
    assert lt.execs["/device:TPU:0"] == [(a + 1000, b + 1000) for a, b in EXECS]
    assert [e.start_ns for e in lt.spans[FETCH]] == [f[0] for f in FETCHES]


def test_the_bracket_closes_on_the_true_offset_and_the_split_reads_it():
    lt = match(planes())
    assert skew_bracket(lt, "/device:TPU:0") == (1000, 1000)
    assert split(lt) == pytest.approx(EXPECTED)


def test_two_devices_each_get_their_own_offset():
    lt = match(planes(deltas=(1000, -2000)))
    assert offsets(lt) == {"/device:TPU:0": 1000, "/device:TPU:1": -2000}
    # the same launches on both devices: the pooled readings are one device's
    assert split(lt) == pytest.approx(EXPECTED)


def test_a_loose_bracket_corrects_by_its_midpoint():
    # fetches that end later than their launch leave room: lo drops below the offset
    late = [(s, e + 40) for s, e in FETCHES]
    lt = match(planes(fetches=late))
    assert skew_bracket(lt, "/device:TPU:0") == (1000 - 40, 1000)
    assert offsets(lt) == {"/device:TPU:0": 980}
    assert offsets(lt, at=0.0) == {"/device:TPU:0": 960}
    # at the bracket's top the clock is the true one: the launches read as built
    edge = split(lt, at=1.0)
    assert edge["launch.to_device_us_p50"] == pytest.approx(EXPECTED["launch.to_device_us_p50"])
    assert edge["launch.return_us_p50"] == pytest.approx(EXPECTED["launch.return_us_p50"] + 40e-3)


def test_an_empty_bracket_refuses_to_read():
    # launch 1's fetch ends before its last operation: no offset explains both
    early = [FETCHES[0], (410, 450), FETCHES[2]]
    lt = match(planes(fetches=early))
    lo, hi = skew_bracket(lt, "/device:TPU:0")
    assert lo > hi
    with pytest.raises(MatchError, match="empty clock-skew bracket"):
        split(lt)


@pytest.mark.parametrize("case,message", [
    ("no decide spans", "no decide span"),
    ("no modules line", "0 executions"),
    ("a module short", "2 executions"),
    ("a fetch short", "2 bench.fetch spans"),
    ("no device", "no device plane"),
])
def test_what_cannot_be_matched_is_refused(case, message):
    ps = planes(decide_spans=case != "no decide spans", modules=case != "no modules line")
    if case == "a module short":
        ps[1] = device_plane("/device:TPU:0", 1000, execs=EXECS[:2])
    elif case == "a fetch short":
        host = ps[0].lines[0].events
        host.remove(next(e for e in host if e.name == FETCH))
    elif case == "no device":
        ps = ps[:1]
    with pytest.raises(MatchError, match=message):
        match(ps)


def test_device_events_move_onto_the_host_clock():
    ps = planes(deltas=(1000, -2000))
    moved = on_host_clock(ps, offsets(match(ps)))
    assert moved[0] is ps[0]
    for p in moved[1:]:
        ops = next(l for l in p.lines if l.name == "XLA Ops").events
        assert (ops[0].start_ns, ops[-1].end_ns) == (EXECS[0][0], EXECS[-1][1])


def _readings(ps, host_names):
    bench = harness.Bench()
    rec = harness.RunRecord({}, {}, {}, 1.0, True)
    rec.device_trace = tracing.reduce(ps, host_names)
    rec.launch_bits = 3 * 1024 * 4096 * 5
    rec.compile_setup_s = 2.5
    names = ("program.compile_s", "net_sweep.gnodebits_per_s", "device.idle_pct.replay")
    return {n: bench.metric(n).read(rec) for n in names}


@pytest.mark.parametrize("host_names", [frozenset(), {"decide", "decide.evidence", "decide.dispatch"}])
def test_the_existing_readers_ignore_the_decide_spans_and_modules_line(host_names):
    bare = _readings(planes((0,), decide_spans=False, modules=False), frozenset())
    assert all(v is not None for v in bare.values())
    assert _readings(planes((0,)), host_names) == bare


def test_traced_decide_spans_reach_the_profilers_trace(tmp_path):
    from repro.bayesnet import by_name, compile_network, sample_evidence
    from repro.obs import Tracer

    spec = by_name("pedestrian-night")
    tr = Tracer(annotate=True)
    net = compile_network(spec, n_bits=256)
    traced = dataclasses.replace(net, trace=tr)
    evid = np.asarray(sample_evidence(spec, jax.random.PRNGKey(1), 8))
    key = jax.random.PRNGKey(2)
    for x in net.decide(key, evid):
        np.asarray(x)
    jax.profiler.start_trace(str(tmp_path), profiler_options=harness._profiler_options())
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                out = traced.decide(key, evid)
                with jax.profiler.TraceAnnotation(FETCH):
                    for x in out:
                        np.asarray(x)
    finally:
        jax.profiler.stop_trace()
    names = [e.name for p in read_xplane(str(tmp_path)) for l in p.lines for e in l.events]
    assert sorted(names) == sorted(["bench.window"] + [FETCH] * 2
                                   + ["decide", "decide.evidence", "decide.dispatch"] * 2)
    assert tr.count("decide.compiles") == 0   # the twin shares the warmed program


def test_a_cpu_run_compares_both_sides_and_profiles_a_window(tmp_path):
    # the chip's run at a tiny size: the device check skipped, and the CPU
    # trace holds no device plane, so nothing is split
    root = Path(__file__).resolve().parents[2]
    doc = json.loads((root / "BENCHMARK.json").read_text())
    (tmp_path / "chipbench" / "configs").mkdir(parents=True)
    (tmp_path / "chipbench" / "traffic").mkdir()
    (tmp_path / "chipbench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "tenants": ["pedestrian-night", "obstacle-class"], "n_bits": 512,
         "chips": 1, "reduced": []}))
    (tmp_path / "chipbench/traffic/tiny-replay.json").write_text(json.dumps(
        {"mode": "replay", "frames_per_chip": 64, "batches_per_network": 2, "in_flight": 2,
         "check_frames": 256}))
    doc["configs"] = [{"name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
                       "reduced": [], "why": "test"}]
    doc["workloads"] = [{"name": "tiny.replay", "config": "tiny", "traffic": "tiny-replay",
                         "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = harness.Bench(tmp_path)
    err = io.StringIO()
    res = measure(bench, bench.cell("tiny.replay"), 2**32 + 5, 0.3, 0.2, 2,
                  t0=time.perf_counter(), require_tpu=False, compile_cache=False, err=err)
    rates = res["decisions_per_s"]
    assert len(rates["untraced"]) == len(rates["traced"]) == 2
    assert all(r > 0 for r in rates["untraced"] + rates["traced"])
    assert res["profiled"]["decide.window_compiles"] == 0
    assert res["device"]["platform"] == "cpu"
    assert "launch split: nothing read: trace holds no device plane" in err.getvalue()


def test_no_tpu_exits_nonzero_without_a_result(monkeypatch, capsys):
    from chipbench.program import backend

    monkeypatch.setattr(backend, "enable_compile_cache", lambda *a, **k: None)
    assert main(["--workload", "fleet-edge.replay", "--seed", str(2**33 + 1)]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err
