"""The trace reduction on a small trace built by hand."""

import pytest

from chipbench.tracing import (
    NO_SPAN, Event, Line, Plane, gaps, op_name, reduce, union_length,
)


def ev(name, start, end):
    return Event(name, float(start), float(end - start))


def trace(device_ops, host, extra_planes=()):
    planes = [
        Plane("/host:CPU", [Line("python", host)]),
        Plane("/device:TPU:0", [Line("XLA Modules", [ev("jit_decide", 0, 1000)]),
                                Line("XLA Ops", device_ops)]),
    ]
    return planes + list(extra_planes)


def test_union_and_gaps():
    iv = [(10, 20), (15, 30), (40, 50), (45, 46), (90, 200)]
    assert union_length(iv, 0, 100) == 20 + 10 + 10
    assert gaps(iv, 0, 100) == [(0, 10), (30, 40), (50, 90)]
    assert union_length([], 0, 100) == 0
    assert gaps([], 0, 100) == [(0, 100)]


def test_busy_kernel_and_idle_by_host_span():
    ops = [ev("net_sweep_pallas.1", 100, 300), ev("fusion.2", 250, 350),
           ev("net_sweep_pallas.1", 600, 700), ev("copy.3", 700, 720)]
    host = [
        ev("bench.window", 100, 1100),
        ev("bench.dispatch", 100, 120),
        ev("bench.fetch", 350, 600),     # device idle 350..600 while the host fetches
        ev("bench.fetch", 720, 1000),
        ev("bench.dispatch", 1000, 1010),
        ev("PjitFunction(decide)", 1001, 1009),   # not a kept name
    ]
    tr = reduce(trace(ops, host))
    assert tr.window_s == pytest.approx(1000e-9)
    # busy union: 100..350 and 600..720
    assert tr.busy_s() == pytest.approx(370e-9)
    secs, n = tr.kernel(r"net_sweep")
    assert n == 2 and secs == pytest.approx(300e-9)
    assert tr.top_ops(2) == [["net_sweep_pallas.1", pytest.approx(300e-9)],
                             ["fusion.2", pytest.approx(100e-9)]]
    idle = tr.idle_by_span()
    # gaps 350..600 (fetch), 720..1100: midpoint 910 lies in the second fetch
    assert idle == {"bench.fetch": pytest.approx(250e-9 + 380e-9)}
    assert tr.top_idle(1)[0][0] == "bench.fetch"


def test_innermost_span_wins_and_uncovered_gaps():
    ops = [ev("k", 0, 10), ev("k", 50, 60), ev("k", 90, 100)]
    host = [ev("bench.window", 0, 200), ev("bench.pump", 5, 80), ev("pack", 20, 40),
            ev("bench.wait", 160, 190)]
    tr = reduce(trace(ops, host), host_names={"pack"})
    idle = tr.idle_by_span()
    assert idle["pack"] == pytest.approx(40e-9)          # gap 10..50, midpoint 30
    assert idle["bench.pump"] == pytest.approx(30e-9)    # gap 60..90, midpoint 75
    assert idle[NO_SPAN] == pytest.approx(100e-9)      # gap 100..200, midpoint 150
    assert "bench.wait" not in idle


def test_several_devices_are_averaged():
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [ev("net_sweep_pallas.1", 0, 50)])])
    ops0 = [ev("net_sweep_pallas.1", 0, 100)]
    tr = reduce(trace(ops0, [ev("bench.window", 0, 100)], [dev1]))
    assert tr.busy_s() == pytest.approx(75e-9)
    assert tr.kernel("net_sweep") == (pytest.approx(150e-9), 2)


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        reduce(trace([ev("k", 0, 1)], []))
    with pytest.raises(ValueError, match="device"):
        reduce([Plane("/host:CPU", [Line("python", [ev("bench.window", 0, 1)])])])


def test_operations_outside_the_window_are_left_out():
    ops = [ev("net_sweep_pallas.1", 0, 50), ev("net_sweep_pallas.1", 100, 150),
           ev("fusion.1", 300, 320)]
    tr = reduce(trace(ops, [ev("bench.window", 100, 200)]))
    assert tr.kernel("net_sweep") == (pytest.approx(50e-9), 1)
    assert tr.top_ops() == [["net_sweep_pallas.1", pytest.approx(50e-9)]]
    assert tr.busy_s() == pytest.approx(50e-9)


def test_op_names_are_shortened_from_their_hlo_text():
    assert op_name("%fusion.2 = f32[1024,2]{1,0} fusion(...)") == "fusion.2"
    assert op_name("net_sweep_pallas.1") == "net_sweep_pallas.1"
