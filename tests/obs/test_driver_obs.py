"""Instrumented FrameDriver / compile_network: bit-identity, span structure,
counters, retry-span parentage, decide spans, and the bounded tracing cost.

The telemetry contract is "gated, not assumed": a traced driver must run the
exact same jax computation as an untraced one (tracing never touches keys or
entropy), an untraced one must touch no telemetry at all, and a traced one
must record a fixed number of spans a launch.  These properties are
regression-tested here rather than trusted; the rate cost of tracing is
measured on the chip.
"""

import jax
import numpy as np
import pytest

from repro.bayesnet import SCENARIOS, by_name, compile_network, sample_evidence
from repro.bayesnet.driver import FrameDriver
from repro.bayesnet.reliability import RetryPolicy
from repro.obs import MetricsRegistry, Tracer

N_BITS = 256
N_FRAMES = 8


def _drivers(net, trace=None, **kw):
    """Same (base_key, salt) with and without telemetry."""
    return (
        FrameDriver(net, salt=7, **kw),
        FrameDriver(net, salt=7, trace=trace or Tracer(),
                    metrics=MetricsRegistry(), **kw),
    )


@pytest.fixture(scope="module")
def pn_net():
    return compile_network(by_name("pedestrian-night"), n_bits=N_BITS)


@pytest.fixture(scope="module")
def pn_net_lowbit():
    # 32-bit streams: decision margins stay small, so Phi(z) confidence never
    # saturates to float 1.0 and min_confidence=1.0 retries every frame
    return compile_network(by_name("pedestrian-night"), n_bits=32)


@pytest.fixture(scope="module")
def pn_ev():
    spec = by_name("pedestrian-night")
    return np.asarray(sample_evidence(spec, jax.random.PRNGKey(1), N_FRAMES))


class TestBitIdentity:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_traced_equals_untraced_every_scenario(self, name):
        spec = by_name(name)
        net = compile_network(spec, n_bits=N_BITS)
        ev = sample_evidence(spec, jax.random.PRNGKey(1), N_FRAMES)
        plain, traced = _drivers(net, max_batch=4)
        plain.submit(ev)
        traced.submit(ev)
        a, b = plain.drain(), traced.drain()
        assert a.keys() == b.keys()
        for rid in a:
            np.testing.assert_array_equal(a[rid][0], b[rid][0])
            assert a[rid][1] == b[rid][1]

    def test_traced_async_equals_untraced_sync(self, pn_net, pn_ev):
        plain, traced = _drivers(pn_net, max_batch=4)
        plain.submit(pn_ev)
        traced.submit(pn_ev)
        a, b = plain.drain(), traced.drain_async()
        for rid in a:
            np.testing.assert_array_equal(a[rid][0], b[rid][0])


class TestSpanStructure:
    def test_launch_span_tree(self, pn_net, pn_ev):
        tr = Tracer()
        drv = FrameDriver(pn_net, max_batch=8, salt=0, trace=tr)
        drv.submit(pn_ev)
        drv.drain()
        (launch,) = tr.named("launch[")
        children = {s.name for s in tr.spans if s.parent_id == launch.span_id}
        assert children == {"pack", "dispatch", "harvest"}
        assert all(s.done for s in tr.spans)
        # the launch span closes after harvest: completion was only observed
        # when the host blocked on the arrays
        harvest = tr.named("harvest")[0]
        assert launch.t_start <= harvest.t_start <= harvest.t_end <= launch.t_end

    def test_async_device_spans_overlap(self, pn_net, pn_ev):
        tr = Tracer()
        drv = FrameDriver(pn_net, max_batch=2, salt=0, trace=tr)
        drv.submit(pn_ev)  # 8 frames / 2 lanes = 4 pipelined launches
        drv.drain_async()
        launches = tr.named("launch[")
        assert len(launches) == 4
        # every dispatch happened before the first harvest blocked: all
        # launch spans were open simultaneously at some point
        assert max(s.t_start for s in launches) < min(s.t_end for s in launches)
        for launch, harvest in zip(launches, tr.named("harvest")):
            assert harvest.parent_id == launch.span_id
            assert harvest.t_end <= launch.t_end

    def test_sync_and_async_traverse_the_same_spans(self, pn_net, pn_ev):
        tra, trb = Tracer(), Tracer()
        da = FrameDriver(pn_net, max_batch=4, salt=3, trace=tra)
        db = FrameDriver(pn_net, max_batch=4, salt=3, trace=trb)
        da.submit(pn_ev)
        db.submit(pn_ev)
        da.drain()
        db.drain_async()
        # same workload, same launches -- only the wall-clock schedule
        # differs; "step" counts differ structurally (async re-steps an
        # empty queue while draining in-flight work)
        ca, cb = tra.span_counts(), trb.span_counts()
        ca.pop("step"), cb.pop("step")
        assert ca == cb

    def test_retry_span_nests_under_flagging_launch(self, pn_net_lowbit, pn_ev):
        tr = Tracer()
        # at 32 bits confidence can't reach 1.0, so min_confidence=1.0
        # retries every frame until the budget is spent
        drv = FrameDriver(
            pn_net_lowbit, max_batch=8, salt=0, trace=tr,
            retry=RetryPolicy(min_confidence=1.0, max_retries=1),
        )
        drv.submit(pn_ev)
        out = drv.drain()
        assert len(out) == N_FRAMES
        retries = tr.named("retry[")
        assert len(retries) == N_FRAMES
        launch0 = tr.named("launch[0]")[0]
        for sp in retries:
            assert sp.parent_id == launch0.span_id  # flagged by launch 0
            assert sp.done and sp.attrs["attempt"] == 1
            assert 0.0 <= sp.attrs["confidence"] < 1.0


class TestDriverMetrics:
    def test_counters_and_hists(self, pn_net, pn_ev):
        mx = MetricsRegistry()
        drv = FrameDriver(pn_net, max_batch=4, salt=0, trace=Tracer(), metrics=mx)
        drv.submit(pn_ev[:6])  # launches of bucket 4 and 2, one padded lane
        drv.drain()
        assert mx.count("frames_in") == 6
        assert mx.count("frames_out") == 6
        assert mx.count("launches") == 2
        assert mx.count("bucket_4") == 1
        assert mx.count("bucket_2") == 1
        assert mx.count("padded_lanes") == 0
        n_nodes = pn_net.spec.n_nodes
        assert mx.count("entropy_words") == (4 + 2) * (N_BITS // 32) * n_nodes
        assert mx.gauges["pending"] == 0
        assert mx.hist("frame_ms").n == 6
        assert mx.hist("launch_ms").n == 2
        assert mx.hist("frame_ms").budget_ms == 0.4
        # the launch watchdog routed through the same registry
        assert mx.count("watch_steps") == 2
        assert mx.hist("watch_step_ms").n == 2

    def test_padded_lanes_counted(self, pn_net, pn_ev):
        mx = MetricsRegistry()
        drv = FrameDriver(pn_net, max_batch=8, salt=0, trace=Tracer(), metrics=mx)
        drv.submit(pn_ev[:5])  # bucket 8, 3 padded lanes
        drv.drain()
        assert mx.count("bucket_8") == 1
        assert mx.count("padded_lanes") == 3

    def test_retry_and_unreliable_counters(self, pn_net_lowbit, pn_ev):
        mx = MetricsRegistry()
        drv = FrameDriver(
            pn_net_lowbit, max_batch=8, salt=0, trace=Tracer(), metrics=mx,
            retry=RetryPolicy(min_confidence=1.0, max_retries=1),
        )
        drv.submit(pn_ev)
        drv.drain()
        assert mx.count("retry_attempt_1") == N_FRAMES
        assert mx.count("retry_launches_attempt_1") == 1
        assert mx.count("flagged_unreliable") == N_FRAMES
        # escalated program compiled once (miss), no rebuild on reuse
        assert mx.count("plan_cache_misses") == 1

    def test_trace_implies_metrics(self, pn_net):
        drv = FrameDriver(pn_net, trace=Tracer())
        assert drv.metrics is not None

    def test_untraced_driver_has_no_registry(self, pn_net):
        drv = FrameDriver(pn_net)
        assert drv.trace is None and drv.metrics is None


class TestCompileTracing:
    def test_compile_span_carries_plan_stats(self):
        tr = Tracer()
        net = compile_network(by_name("pedestrian-night"), n_bits=N_BITS, trace=tr)
        (sp,) = tr.named("compile_network")
        assert sp.done and sp.attrs["network"] == "pedestrian-night"
        assert sp.attrs["n_nodes"] == net.spec.n_nodes
        assert sp.attrs["n_bits"] == N_BITS
        assert sp.attrs["cpt_rows"] > 0
        assert sp.attrs["threshold_mask_bytes"] > 0
        assert sp.attrs["n_value_slots"] == len(net.queries)  # binary queries

    def test_trace_none_is_default(self):
        net = compile_network(by_name("pedestrian-night"), n_bits=N_BITS)
        assert net is not None  # no tracer anywhere in the default path


class TestDecideTracing:
    """``compile_network(trace=)``: the program records one span tree a call."""

    def test_decide_span_tree_and_attrs(self, pn_ev):
        tr = Tracer()
        net = compile_network(by_name("pedestrian-night"), n_bits=N_BITS, trace=tr)
        key = jax.random.PRNGKey(5)
        out = net.decide(key, pn_ev)
        dec = next(s for s in tr.spans if s.name == "decide")
        evid, disp = (s for s in tr.spans if s.parent_id == dec.span_id)
        assert (evid.name, disp.name) == ("decide.evidence", "decide.dispatch")
        assert dec.parent_id is None and all(s.done for s in tr.spans)
        n_ev = len(net.evidence)
        assert dec.attrs == {
            "network": "pedestrian-night", "launch": 0, "frames": N_FRAMES,
            "in_bytes": N_FRAMES * n_ev * 4 + key.nbytes,
            "out_arrays": 3, "out_bytes": sum(x.nbytes for x in out),
        }
        net.decide(key, pn_ev)
        assert [s.attrs["launch"] for s in tr.spans if s.name == "decide"] == [0, 1]

    def test_run_records_the_same_tree(self, pn_ev):
        tr = Tracer()
        net = compile_network(by_name("pedestrian-night"), n_bits=N_BITS, trace=tr)
        net.run(jax.random.PRNGKey(5), pn_ev)
        assert tr.span_counts() == {
            "compile_network": 1, "run": 1, "run.evidence": 1, "run.dispatch": 1,
        }
        assert tr.named("run")[0].attrs["out_arrays"] == 2
        assert tr.count("run.compiles") == 1 and tr.count("decide.compiles") == 0

    def test_compiled_flag_and_count(self, pn_ev):
        tr = Tracer()
        net = compile_network(by_name("pedestrian-night"), n_bits=N_BITS, trace=tr)
        key = jax.random.PRNGKey(5)
        compiled = []
        for ev in (pn_ev, pn_ev, pn_ev[:4], pn_ev[:4]):   # new shape, repeat, new, repeat
            net.decide(key, ev)
            compiled.append(tr.named("decide.dispatch")[-1].attrs["compiled"])
        assert compiled == [True, False, True, False]
        assert tr.count("decide.compiles") == 2

    @pytest.mark.parametrize("fused", [True, False])
    def test_trace_none_records_nothing_and_is_bit_identical(self, pn_ev, fused):
        spec = by_name("pedestrian-night")
        tr = Tracer()
        plain = compile_network(spec, n_bits=N_BITS, fused=fused)
        traced = compile_network(spec, n_bits=N_BITS, fused=fused, trace=tr)
        assert plain.trace is None and traced.trace is tr
        key = jax.random.PRNGKey(9)
        for a, b in zip(plain.decide(key, pn_ev), traced.decide(key, pn_ev)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(plain.run(key, pn_ev), traced.run(key, pn_ev)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert tr.span_counts() == {
            "compile_network": 1, "decide": 1, "decide.evidence": 1,
            "decide.dispatch": 1, "run": 1, "run.evidence": 1, "run.dispatch": 1,
        }


def _trip(*_, **__):
    raise AssertionError("the untraced path touched a tracer or registry")


class TestOverhead:
    def test_tracing_overhead_within_five_percent(self, pn_net, pn_ev, monkeypatch):
        # What tracing costs is bounded by construction, and that is what a
        # CPU test can show: untraced, neither the driver nor the program
        # builds or touches a Tracer or MetricsRegistry (every method
        # raises here); traced, each launch records a fixed, small number
        # of spans.  The 5 % bound on the traced rate is a device-clock
        # number and is measured on the chip (PERF.md), not on a shared CPU.
        for cls, names in ((Tracer, ("__init__", "begin", "end", "span", "event", "inc")),
                           (MetricsRegistry, ("__init__", "inc", "set_gauge", "hist", "observe"))):
            for name in names:
                monkeypatch.setattr(cls, name, _trip)
        key = jax.random.PRNGKey(5)
        pn_net.decide(key, pn_ev)
        pn_net.run(key, pn_ev)
        for drain in ("drain", "drain_async"):
            drv = FrameDriver(pn_net, max_batch=2, salt=0)
            drv.submit(pn_ev)
            assert len(getattr(drv, drain)()) == N_FRAMES
        monkeypatch.undo()

        tr = Tracer()
        net = compile_network(by_name("pedestrian-night"), n_bits=N_BITS, trace=tr)
        drv = FrameDriver(net, max_batch=2, salt=0, trace=tr)
        drv.submit(pn_ev)   # 4 launches
        drv.drain_async()
        counts = tr.span_counts()
        per_launch = ("pack", "dispatch", "run", "run.evidence", "run.dispatch", "harvest")
        assert sum(n for k, n in counts.items() if k.startswith("launch[")) == 4
        assert {k: counts[k] for k in per_launch} == dict.fromkeys(per_launch, 4)
        assert set(counts) - set(per_launch) - {f"launch[{i}]" for i in range(4)} == {
            "compile_network", "submit", "step",
        }
