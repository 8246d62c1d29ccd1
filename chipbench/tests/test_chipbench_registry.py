"""Configs, mixes and metrics are found by name; new ones come from new files alone."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.harness import Bench, BenchError

ROOT = Path(__file__).resolve().parents[2]


def test_every_cell_resolves():
    bench = Bench()
    for w in bench.doc["workloads"]:
        cell = bench.cell(w["name"])
        config = bench.config(cell["config"])
        traffic = bench.traffic(cell["traffic"])
        assert harness.load_mode(traffic["mode"]) is not None
        assert config["name"] == cell["config"] and config["chips"] == cell["chips"]
        for traced in (False, True):
            defs = bench.metrics_for(cell["name"], traced)
            assert defs, (cell["name"], traced)
            for m in defs:
                assert callable(bench.metric(m["name"]).read)
        names = {m["name"] for m in bench.metrics_for(cell["name"], False)}
        assert "setup_s" in names and len(names) >= 2


def test_every_metric_has_a_reader_and_reads_nothing_from_an_empty_run():
    bench = Bench()
    rec = harness.RunRecord({}, {}, {}, 1.0, True)
    for m in bench.doc["end_to_end"] + bench.doc["per_layer"]:
        v = bench.metric(m["name"]).read(rec)
        assert v is None or v != v, m["name"]


@pytest.mark.parametrize("kind,name", [
    ("cell", "nope.replay"), ("config", "nope"), ("traffic", "nope"), ("metric", "nope_ms"),
])
def test_unknown_names_are_errors(kind, name):
    bench = Bench()
    with pytest.raises(BenchError):
        getattr(bench, kind)(name)


def test_unknown_mode_is_an_error():
    with pytest.raises(BenchError):
        harness.load_mode("nope")


def test_unknown_workload_exits_nonzero(capsys):
    assert harness.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_new_config_mix_and_metric_from_new_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    # the new files
    cfg = json.loads((tmp_path / "chipbench/configs/fleet-edge.json").read_text())
    cfg.update(name="two-nets", tenants=cfg["tenants"][:2])
    (tmp_path / "chipbench/configs/two-nets.json").write_text(json.dumps(cfg))
    mix = json.loads((tmp_path / "chipbench/traffic/replay.json").read_text())
    mix.update(in_flight=3)
    (tmp_path / "chipbench/traffic/deep.json").write_text(json.dumps(mix))
    (tmp_path / "chipbench/metrics/bench.launches.py").write_text(
        "def read(rec):\n    return getattr(rec, 'launches', None)\n")
    # the new entries
    doc["configs"].append({"name": "two-nets", "source": "https://arxiv.org/abs/2412.06838",
                           "file": "chipbench/configs/two-nets.json", "reduced": ["tenants"],
                           "why": "test"})
    doc["workloads"].append({"name": "two-nets.deep", "config": "two-nets",
                             "traffic": "deep", "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if "fleet-edge.replay" in m.get("workloads", ()):
            m["workloads"].append("two-nets.deep")
    doc["per_layer"].append({"name": "bench.launches", "unit": "count", "better": "higher",
                             "source": "program_counter", "layer": "benchmark",
                             "moves": "decisions_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    bench = Bench(tmp_path)
    cell = bench.cell("two-nets.deep")
    assert len(bench.config(cell["config"])["tenants"]) == 2
    assert bench.traffic(cell["traffic"])["in_flight"] == 3
    e2e = {m["name"] for m in bench.metrics_for(cell["name"], False)}
    assert e2e == {"setup_s", "decisions_per_s"}
    layer = {m["name"] for m in bench.metrics_for(cell["name"], True)}
    # a metric with no workloads key goes to every cell reporting what it moves
    assert {"bench.launches", "program.compile_s"} <= layer
    assert "bench.launches" in {m["name"] for m in bench.metrics_for("fleet-edge.replay", True)}
    rec = harness.RunRecord({}, {}, {}, 1.0, True)
    assert bench.metric("bench.launches").read(rec) is None
    rec.launches = 7              # a mode's own attribute, for its own readers
    assert bench.metric("bench.launches").read(rec) == 7


def _trace():
    from chipbench.tracing import DeviceTrace, Event

    ops = {"/device:TPU:0": [Event("net_sweep_pallas.1", 0.0, 2e9), Event("fusion.1", 2e9, 1e9)]}
    return DeviceTrace(0.0, 4e9, ops, [])


@pytest.mark.parametrize("name,fields,value", [
    ("decisions_per_s", {"decisions": 1000, "t_window": 1.0, "t_end": 3.0}, 500.0),
    ("setup_s", {"setup_s": 12.5}, 12.5),
    ("program.compile_s", {"compile_setup_s": 2.25}, 2.25),
    ("device.idle_pct.replay", {"device_trace": _trace()}, 25.0),
    ("net_sweep.gnodebits_per_s", {"device_trace": _trace(), "launch_bits": 8e9}, 4.0),
    ("device.idle_pct.served", {"device_trace": _trace()}, 25.0),
    ("latency_p50_ms", {"latency_ms": [4.0, 1.0, 3.0, 2.0]}, 2.5),
    ("latency_p95_ms", {"latency_ms": [float(x) for x in range(101)]}, 95.0),
    ("served.lag_p99_ms", {"lag_ms": [0.5] * 99 + [100.5]}, 1.5),
    ("router.pump_us_per_decision",
     {"program_spans": [("router.pump", 1.0, 1.003), ("pack", 1.0, 1.001), ("router.pump", 2.0, 2.001)],
      "answered": 8}, 500.0),
    ("driver.host_us_per_launch",
     {"program_spans": [("launch[0]", 0.0, 0.01), ("pack", 0.0, 0.0001), ("dispatch", 0.001, 0.002),
                        ("harvest", 0.003, 0.0049), ("launch[1]", 1.0, 1.01), ("run", 1.0, 1.5)]},
     1500.0),
    ("driver.frames_per_launch", {"program_counts": {"launches": 4, "frames_out": 22}}, 5.5),
])
def test_metric_readers_on_a_record(name, fields, value):
    rec = harness.RunRecord({}, {}, {}, 1.0, True)
    for k, v in fields.items():
        setattr(rec, k, v)
    assert Bench().metric(name).read(rec) == pytest.approx(value)


def test_compile_seconds_are_the_union_of_nested_events():
    log = harness.CompileLog()
    # (name, duration, host time it ended): a lowering inside a compile, and one after set-up
    log.events = [("/jax/core/compile/a", 1.0, 11.0), ("/jax/core/compile/b", 0.5, 10.8),
                  ("/jax/core/compile/c", 0.25, 12.0), ("/jax/core/compile/d", 3.0, 30.0)]
    assert log.seconds(0.0, 20.0) == pytest.approx(1.25)
