"""Tracer: nesting, async span lifecycle, and Chrome-trace export schema."""

import json

import numpy as np
import pytest

from repro.obs import Tracer


def fake_clock():
    """Deterministic monotone clock: 1ms per reading."""
    t = [0.0]

    def clock():
        t[0] += 1e-3
        return t[0]

    return clock


class TestSyncSpans:
    def test_nesting_follows_the_with_stack(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("outer"):
            with tr.span("mid"):
                with tr.span("inner"):
                    pass
            with tr.span("mid2"):
                pass
        outer, mid, inner, mid2 = tr.spans
        assert outer.parent_id is None
        assert mid.parent_id == outer.span_id
        assert inner.parent_id == mid.span_id
        assert mid2.parent_id == outer.span_id
        assert all(s.done for s in tr.spans)
        # children are contained in their parent's interval
        assert outer.t_start < mid.t_start and mid.t_end < outer.t_end

    def test_span_attrs_and_duration(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("work", n=3) as sp:
            sp.attrs["extra"] = "late"
        assert sp.attrs == {"n": 3, "extra": "late"}
        assert sp.dur_ms == pytest.approx(1.0)

    def test_span_closed_even_on_exception(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.spans[0].done
        assert tr.open_spans == []


class TestAsyncSpans:
    def test_begin_end_lifecycle(self):
        tr = Tracer(clock=fake_clock())
        sid = tr.begin("device", parent=None, track="device", ticket=0)
        assert not tr.get(sid).done
        assert tr.open_spans == [tr.get(sid)]
        sp = tr.end(sid, ok=True)
        assert sp.done and sp.attrs == {"ticket": 0, "ok": True}

    def test_double_end_raises(self):
        tr = Tracer()
        sid = tr.begin("x", parent=None)
        tr.end(sid)
        with pytest.raises(ValueError, match="already ended"):
            tr.end(sid)

    def test_async_span_defaults_to_enclosing_sync_parent(self):
        tr = Tracer()
        with tr.span("step") as step:
            sid = tr.begin("launch")
        assert tr.get(sid).parent_id == step.span_id

    def test_overlapping_async_spans_coexist(self):
        # the dispatch/harvest split: N launches open before any closes
        tr = Tracer(clock=fake_clock())
        sids = [tr.begin(f"device[{i}]", parent=None, track="device")
                for i in range(3)]
        assert len(tr.open_spans) == 3
        for sid in sids:
            tr.end(sid)
        starts = [tr.get(s).t_start for s in sids]
        ends = [tr.get(s).t_end for s in sids]
        assert max(starts) < min(ends)  # genuinely overlapping intervals

    def test_instant_event(self):
        tr = Tracer()
        sid = tr.event("submit", n=4)
        sp = tr.get(sid)
        assert sp.instant and sp.done and sp.t_start == sp.t_end

    def test_named_and_counts(self):
        tr = Tracer()
        with tr.span("launch[0]"):
            pass
        with tr.span("launch[1]"):
            pass
        tr.event("submit")
        assert [s.name for s in tr.named("launch")] == ["launch[0]", "launch[1]"]
        assert tr.span_counts() == {"launch[0]": 1, "launch[1]": 1, "submit": 1}

    def test_named_counts_start_at_zero_and_add(self):
        tr = Tracer()
        assert tr.count("decide.compiles") == 0
        assert tr.inc("decide.compiles") == 1
        assert tr.inc("decide.compiles", 2) == 3
        assert tr.counters == {"decide.compiles": 3}
        assert tr.spans == []   # counts are not spans


class TestChromeTraceExport:
    def _trace(self):
        tr = Tracer(clock=fake_clock())
        with tr.span("step", block=False):
            with tr.span("dispatch", bucket=np.int64(8)):
                pass
            tr.begin("device", track="device", shape=(8, 3))
        tr.event("submit", n=2)
        return tr

    def test_schema_is_valid_chrome_trace(self, tmp_path):
        tr = self._trace()
        path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            doc = json.load(f)  # must round-trip as strict JSON
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        metas = [e for e in events if e["ph"] == "M"]
        spans = [e for e in events if e["ph"] == "X"]
        instants = [e for e in events if e["ph"] == "i"]
        assert {m["args"]["name"] for m in metas} == {"host", "device"}
        assert all(m["name"] == "thread_name" for m in metas)
        # metadata events precede payload events
        assert events[: len(metas)] == metas
        assert len(spans) == 3 and len(instants) == 1
        for e in spans:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["args"]["span_id"], int)
        # numpy attrs were coerced to plain JSON types
        disp = next(e for e in spans if e["name"] == "dispatch")
        assert disp["args"]["bucket"] == 8
        dev = next(e for e in spans if e["name"] == "device")
        assert dev["args"]["shape"] == [8, 3]

    def test_parent_ids_survive_export(self):
        tr = self._trace()
        events = tr.to_chrome_trace()["traceEvents"]
        by_name = {e["name"]: e for e in events if e["ph"] != "M"}
        step_id = by_name["step"]["args"]["span_id"]
        assert by_name["dispatch"]["args"]["parent_id"] == step_id
        assert by_name["device"]["args"]["parent_id"] == step_id
        assert "parent_id" not in by_name["step"]["args"]

    def test_unfinished_spans_export_flagged_not_dropped(self):
        tr = self._trace()  # the "device" span is still open
        events = tr.to_chrome_trace()["traceEvents"]
        dev = next(e for e in events if e.get("name") == "device")
        assert dev["args"]["unfinished"] is True
        assert dev["dur"] == 0.0

    def test_timestamps_relative_to_first_span(self):
        tr = self._trace()
        events = [e for e in tr.to_chrome_trace()["traceEvents"] if e["ph"] != "M"]
        assert min(e["ts"] for e in events) == 0.0
