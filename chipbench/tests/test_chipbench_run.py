"""Whole runs on the CPU at a small size: the chip check skipped, the rest as on the chip.

A sound program is ``correct``; the control (a quarter of the stated
``n_bits``) is not, and neither is a run with the timed path broken
underneath: half of each batch left out, the exchange between chips left
out, an answer altered where it is produced, answers lost, answers served
at a quarter of the bits.
"""

import io
import json
import shutil
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
CONFIG = {
    "name": "tiny", "tenants": ["pedestrian-night", "obstacle-class"], "n_bits": 512,
    "max_batch": 2, "deadline_ms": 100, "chips": 1, "reduced": [],
}
MIXES = {
    "tiny-replay": {"mode": "replay", "frames_per_chip": 64, "batches_per_network": 2,
                    "in_flight": 2, "check_frames": 2048},
    "tiny-ticks": {"mode": "served", "cameras": 2, "camera_hz": 10, "objects_per_tick_mean": 2,
                   "object_networks": ["pedestrian-night"], "scene_networks": ["obstacle-class"],
                   "drain_s": 1.0},
}
# the committed cell each tiny cell stands in for: its metrics go to the tiny cell
CELLS = {"fleet-edge.replay": "tiny.tiny-replay", "vehicle-nuscenes.ticks": "tiny.tiny-ticks"}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "chipbench" / "metrics", root / "chipbench" / "metrics")
    (root / "chipbench" / "configs").mkdir()
    (root / "chipbench" / "traffic").mkdir()
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(CONFIG))
    for name, mix in MIXES.items():
        (root / f"chipbench/traffic/{name}.json").write_text(json.dumps(mix))
    doc["configs"] = [{"name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
                       "reduced": [], "why": "test"}]
    doc["workloads"] = [{"name": f"tiny.{m}", "config": "tiny", "traffic": m, "chips": 1, "why": "test"}
                        for m in MIXES]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELLS[w] for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return harness.Bench(root)


def run(bench, cell, control=None, seed=2**32 + 17):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(bench, bench.cell(cell), seed, 1.0, False, t0=0.0,
                           control=control, require_tpu=False, compile_cache=False,
                           out=out, err=err)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    assert list(res)[-1] == "check"
    assert err.getvalue().strip().splitlines()[-1].startswith("check frames_missing")
    return res


@pytest.mark.parametrize("seed", [2**32 + 17, 3])
def test_a_sound_run_is_correct(bench, seed):
    res = run(bench, "tiny.tiny-replay", seed=seed)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "decisions_per_s"}
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("seed", [2**32 + 17, 3])
def test_the_control_is_not_correct(bench, seed):
    res = run(bench, "tiny.tiny-replay", control="quarter-bits", seed=seed)
    assert not res["correct"]
    assert res["check"]["acc_z2"]["value"] > res["check"]["acc_z2"]["limit"]


def _half_batch(out):
    """Half of the batch left out: its answers are copies of the other half's."""
    b = out[0].shape[0] // 2
    return tuple(jnp.concatenate([x[:b]] * 2) for x in out)


def _no_exchange(out, shards=4):
    """The exchange between chips left out: every shard's rows are shard 0's."""
    b = out[0].shape[0] // shards
    return tuple(jnp.concatenate([x[:b]] * shards) for x in out)


def _altered(out):
    """One answer altered where it is produced: the first frame's decisions flipped."""
    post, dec, acc = out
    return post, dec.at[0].set(1 - jnp.minimum(dec[0], 1)), acc


def _lost(out):
    """Answers lost: the launch returns its last frame short."""
    return tuple(x[:-1] for x in out)


@pytest.mark.parametrize("fault", [_half_batch, _no_exchange, _altered, _lost])
def test_a_broken_decide_is_not_correct(bench, monkeypatch, fault):
    from repro.bayesnet.compile import CompiledNetwork

    decide = CompiledNetwork.decide
    monkeypatch.setattr(CompiledNetwork, "decide",
                        lambda self, key, ev: fault(decide(self, key, ev)))
    assert not run(bench, "tiny.tiny-replay")["correct"]


def test_every_network_is_checked(bench, monkeypatch):
    from chipbench import check

    seen = []
    add = check.Comparison.add

    def spy(self, net, *args, **kw):
        seen.append(net.name)
        return add(self, net, *args, **kw)

    monkeypatch.setattr(check.Comparison, "add", spy)
    assert run(bench, "tiny.tiny-replay")["correct"]
    assert set(seen) == set(CONFIG["tenants"])


def test_no_tpu_exits_nonzero_without_a_result(monkeypatch, capsys):
    from chipbench.program import backend

    monkeypatch.setattr(backend, "enable_compile_cache", lambda *a, **k: None)
    rc = harness.main(["--workload", "fleet-edge.replay", "--seed", str(2**33 + 1),
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


SERVED = {"setup_s", "latency_p50_ms", "latency_p95_ms"}


@pytest.mark.parametrize("seed", [2**32 + 17, 3])
def test_a_sound_served_run_is_correct(bench, seed):
    res = run(bench, "tiny.tiny-ticks", seed=seed)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == SERVED
    p50, p95 = (res["metrics"][k]["value"] for k in ("latency_p50_ms", "latency_p95_ms"))
    assert 0 < p50 <= p95 < 1000.0


@pytest.mark.parametrize("seed", [2**32 + 17, 3])
def test_the_served_control_is_not_correct(bench, seed):
    res = run(bench, "tiny.tiny-ticks", control="quarter-bits", seed=seed)
    assert not res["correct"]
    assert res["check"]["acc_z2"]["value"] > res["check"]["acc_z2"]["limit"]


def _run_altered(run_fn):
    """An answer altered where it is produced: the first frame's posterior turned over."""
    def run(self, key, ev):
        post, acc = run_fn(self, key, ev)
        return post.at[0].set(1 - post[0]), acc
    return run


def _run_half_batch(run_fn):
    """Half of the launch left out: its answers are copies of the first half's."""
    def run(self, key, ev):
        out = run_fn(self, key, ev)
        b = -(-out[0].shape[0] // 2)
        return tuple(jnp.concatenate([x[:b]] * 2)[:x.shape[0]] for x in out)
    return run


@pytest.mark.parametrize("fault", [_run_altered, _run_half_batch])
def test_a_broken_served_launch_is_not_correct(bench, monkeypatch, fault):
    from repro.bayesnet.compile import CompiledNetwork

    monkeypatch.setattr(CompiledNetwork, "run", fault(CompiledNetwork.run))
    assert not run(bench, "tiny.tiny-ticks")["correct"]


def test_answers_served_at_a_quarter_of_the_bits_are_not_correct(bench, monkeypatch):
    from repro.serve import router

    compile_network = router.compile_network
    monkeypatch.setattr(router, "compile_network",
                        lambda spec, n_bits, **kw: compile_network(spec, n_bits // 4, **kw))
    res = run(bench, "tiny.tiny-ticks")
    assert not res["correct"] and res["failed"] == 0


def test_a_dropped_result_is_failed_later_than_every_answer_and_not_correct(bench, monkeypatch):
    from repro.serve.router import BayesRouter

    harvest, dropped, recs = BayesRouter.harvest, [], []

    def drop_one(self):
        fresh = harvest(self)
        rid = max(fresh, default=None)
        if rid is not None and not dropped and self.requests[rid].deadline < time.perf_counter() + 60:
            dropped.append(fresh.pop(rid))   # a window frame's, not a set-up launch's
        return fresh

    class Record(harness.RunRecord):
        def __init__(self, *args):
            super().__init__(*args)
            recs.append(self)

    monkeypatch.setattr(BayesRouter, "harvest", drop_one)
    monkeypatch.setattr(harness, "RunRecord", Record)
    res = run(bench, "tiny.tiny-ticks")
    assert len(dropped) == 1
    assert not res["correct"] and res["check"]["frames_missing"]["value"] == 1
    assert res["failed"] == 1
    lat = sorted(recs[0].latency_ms)
    # the dropped frame ranks above every answered one: the whole time the run observed
    assert lat[-1] == (recs[0].t_end - recs[0].t_window) * 1e3 > lat[-2]


def test_a_host_stall_makes_frames_late_not_failed(bench, monkeypatch):
    from repro.serve.router import BayesRouter

    pump, stalled, recs = BayesRouter.pump, [], []

    def stall_once(self):
        in_window = any(r.deadline < time.perf_counter() + 60 for r in self.requests.values())
        if in_window and not stalled:
            stalled.append(time.sleep(0.3))   # the host stands still past the 100 ms deadline
        return pump(self)

    class Record(harness.RunRecord):
        def __init__(self, *args):
            super().__init__(*args)
            recs.append(self)

    monkeypatch.setattr(BayesRouter, "pump", stall_once)
    monkeypatch.setattr(harness, "RunRecord", Record)
    res = run(bench, "tiny.tiny-ticks")
    assert stalled
    assert res["correct"] and res["failed"] == 0
    # the frames due in the stall are answered late, and their latency counts the wait
    assert max(recs[0].latency_ms) >= 300.0


def test_a_traced_served_window_feeds_every_per_layer_reader(bench):
    from chipbench.modes import served

    cell = bench.cell("tiny.tiny-ticks")
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    rec = harness.RunRecord(cell, config, traffic, 1.0, True)
    ctx = harness.Context(cell, config, traffic, 2**33 + 3, 1.0, True, None, rec,
                          require_tpu=False)
    mode = served.Mode(ctx)
    mode.setup()
    mode.window()
    names = [m["name"] for m in bench.metrics_for(cell["name"], True)
             if cell["name"] in m.get("workloads", ()) and m["source"] != "device_trace"]
    assert {"served.lag_p99_ms", "router.pump_us_per_decision", "driver.host_us_per_launch",
            "driver.frames_per_launch"} <= set(names)
    for name in names:
        assert bench.metric(name).read(rec) > 0, name
    notes = mode.notes()
    assert notes["run.compiles in the window"] == 0 and notes["compile events in the window"] == 0
    assert sum(notes["launches per bucket"].values()) == sum(notes["launches per network"].values())
    assert rec.failed == 0 and rec.answered == rec.attempted
