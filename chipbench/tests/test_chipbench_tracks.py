"""The tracked cell at a tiny size on the CPU, the chip check skipped.

A sound tracker is ``correct``; the comparison finds a belief that is not
carried (the published prior at every frame), another track's belief, an
empty sample that writes its (all-zero) posterior into its track, and the
quarter-bits control.
"""

import io
import json
import shutil
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import Network
from chipbench.track_reference import Tracked, transition

ROOT = Path(__file__).resolve().parents[2]
CONFIG = {
    "name": "tiny-track", "tenants": ["pedestrian-night", "obstacle-class"],
    "tracked": ["pedestrian", "obstacle"], "transition_stay": 0.95, "max_tracks": 16,
    "n_bits": 512, "max_batch": 4, "deadline_ms": 100, "chips": 1, "reduced": [],
}
MIX = {"mode": "tracks", "cameras": 2, "camera_hz": 10, "objects_per_tick_mean": 2,
       "object_networks": ["pedestrian-night", "obstacle-class"], "scene_networks": [],
       "track_mean_ticks": 6, "drain_s": 2.0}
CELL = "tiny-track.tracks"
STAND_INS = {"vehicle-nuscenes-track.tracks": CELL}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "chipbench" / "metrics", root / "chipbench" / "metrics")
    (root / "chipbench" / "configs").mkdir()
    (root / "chipbench" / "traffic").mkdir()
    (root / f"chipbench/configs/{CONFIG['name']}.json").write_text(json.dumps(CONFIG))
    (root / "chipbench/traffic/tracks.json").write_text(json.dumps(MIX))
    doc["configs"] = [{"name": CONFIG["name"], "source": "test",
                       "file": f"chipbench/configs/{CONFIG['name']}.json", "reduced": [], "why": "test"}]
    doc["workloads"] = [{"name": CELL, "config": CONFIG["name"], "traffic": "tracks",
                         "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [STAND_INS.get(w, w) for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return harness.Bench(root)


def run(bench, cell=CELL, control=None, seed=2**32 + 17, seconds=2.0, traced=False):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(bench, bench.cell(cell), seed, seconds, traced, t0=0.0,
                           control=control, require_tpu=False, compile_cache=False,
                           out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) == json.loads(json.dumps(res))
    return res, err.getvalue()


@pytest.mark.parametrize("seed", [2**32 + 17, 2**33 + 5])
def test_a_sound_tracked_run_is_correct(bench, seed):
    res, err = run(bench, seed=seed)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "latency_p50_ms", "latency_p95_ms"}
    # tracks were carried, and every frame of them was compared
    fresh, carried = json.loads(err.split("frames fresh / carried: ")[1].splitlines()[0])
    assert fresh > 0 and carried > 0
    assert "frames not compared (an earlier frame of the track not OK): 0" in err


def test_the_tracked_control_is_not_correct(bench):
    res, _ = run(bench, control="quarter-bits")
    assert not res["correct"]
    assert res["check"]["acc_z2"]["value"] > res["check"]["acc_z2"]["limit"]


def _every_frame_fresh(monkeypatch):
    """The belief not carried: every frame decided under the published prior."""
    from repro.bayesnet.driver import FrameDriver

    pack = FrameDriver._pack_tracks

    def fresh(self, taken, bucket):
        idx, _ = pack(self, taken, bucket)
        return idx, np.ones(bucket, bool)

    monkeypatch.setattr(FrameDriver, "_pack_tracks", fresh)


def _another_tracks_belief(monkeypatch):
    """Each frame predicted from the next table row's belief, written to its own."""
    from repro.bayesnet.compile import CompiledNetwork

    run_fn = CompiledNetwork.run

    def run(self, key, ev, table=None, track_idx=None, fresh=None):
        if table is None:
            return run_fn(self, key, ev)
        post, acc, out = run_fn(self, key, ev, jnp.roll(table, -1, axis=0), track_idx, fresh)
        idx = jnp.asarray(track_idx)
        return post, acc, table.at[idx].set(out.at[idx].get(mode="clip"), mode="drop")

    monkeypatch.setattr(CompiledNetwork, "run", run)


def _empty_sample_writes_its_posterior(monkeypatch):
    """A frame that accepted no sample leaves its all-zero posterior as the belief."""
    from repro.bayesnet import compile as compile_mod

    monkeypatch.setattr(compile_mod, "carry_belief", lambda post, pred, accepted: post)


@pytest.mark.parametrize("fault", [_every_frame_fresh, _another_tracks_belief])
def test_a_broken_tracker_is_not_correct(bench, monkeypatch, fault):
    fault(monkeypatch)
    assert not run(bench)[0]["correct"]


def _rare_evidence(monkeypatch, every=4):
    """Traffic where empty samples are common: every ``every``-th continuing frame
    shows its network's least likely evidence."""
    from chipbench.modes import tracks

    schedule = tracks.track_schedule

    def rare(mix, refs, seconds, seed):
        ticks, submits, needed = schedule(mix, refs, seconds, seed)
        frames = [rows for tick in ticks for _, rows in tick.frames]   # the submits' order
        n = 0
        for s, rows in zip(submits, frames):
            net = Network.load(s.net)
            for i in np.flatnonzero(~s.fresh):
                n += 1
                if n % every == 0:
                    rows[i] = net.pattern_rows([int(np.argmin(net.p_e))])[0]
        return ticks, submits, needed

    monkeypatch.setattr(tracks, "track_schedule", rare)


def test_an_empty_sample_that_writes_its_posterior_is_not_correct(bench, monkeypatch):
    _rare_evidence(monkeypatch)
    sound, err = run(bench)
    held = int(err.split("frames held (accepted 0, kept the prediction): ")[1].split()[0])
    assert sound["correct"] and held > 0, (sound["check"], held)
    _empty_sample_writes_its_posterior(monkeypatch)
    broken = run(bench)[0]
    assert not broken["correct"], broken["check"]


def test_a_traced_tracked_window_feeds_the_tracker_readers(bench):
    from chipbench.modes import tracks

    cell = bench.cell(CELL)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    rec = harness.RunRecord(cell, config, traffic, 1.0, True)
    ctx = harness.Context(cell, config, traffic, 2**33 + 3, 1.0, True, None, rec,
                          require_tpu=False)
    mode = tracks.Mode(ctx)
    mode.setup()
    mode.window()
    assert "track.index" in mode.host_span_names
    for name in ("track.host_us_per_launch", "router.pump_us_per_decision",
                 "driver.host_us_per_launch", "driver.frames_per_launch", "served.lag_p99_ms"):
        assert bench.metric(name).read(rec) > 0, name
    assert bench.metric("track.deferred_per_kframe").read(rec) >= 0
    counts = rec.program_counts
    frames = counts["track.carried"] + counts["track.fresh"]
    assert frames == rec.attempted and counts["track.carried"] > 0
    notes = mode.notes()
    assert notes["run.compiles in the window"] == 0 and notes["compile events in the window"] == 0
    assert rec.failed == 0 and rec.answered == rec.attempted


@pytest.mark.parametrize("name,fields,value", [
    ("track.host_us_per_launch",
     {"program_spans": [("launch[0]", 0.0, 0.01), ("track.index", 0.0, 0.00002),
                        ("launch[1]", 1.0, 1.01), ("track.index", 1.0, 1.00004)]}, 30.0),
    ("track.deferred_per_kframe",
     {"program_counts": {"track.carried": 1500, "track.fresh": 500, "track.deferred": 3}}, 1.5),
    ("track.deferred_per_kframe", {"program_counts": {"track.carried": 10, "track.fresh": 10}}, 0.0),
])
def test_tracker_readers_on_a_record(name, fields, value):
    rec = harness.RunRecord({}, {}, {}, 1.0, True)
    for k, v in fields.items():
        setattr(rec, k, v)
    assert harness.Bench().metric(name).read(rec) == pytest.approx(value)


@pytest.mark.parametrize("name", ["track.host_us_per_launch", "track.deferred_per_kframe"])
def test_tracker_readers_read_nothing_from_an_empty_record(name):
    assert harness.Bench().metric(name).read(harness.RunRecord({}, {}, {}, 1.0, True)) is None


def test_a_program_without_tracked_networks_fails_at_once(bench, monkeypatch):
    """The parent program's specs have no state: the mode refuses before set-up."""
    import dataclasses

    from chipbench import program

    @dataclasses.dataclass(frozen=True)
    class UntrackedSpec:
        name: str

    monkeypatch.setattr(program, "spec", lambda doc: UntrackedSpec(doc["name"]))
    with pytest.raises(harness.BenchError, match="no tracked networks"):
        run(bench)


def test_the_schedule_carries_tracks_and_clamps_the_class():
    from chipbench.modes.tracks import track_schedule

    refs = {t: Tracked(json.loads((ROOT / f"chipbench/networks/{t}.json").read_text()), root, 0.95)
            for t, root in (("pedestrian-night", "pedestrian"), ("obstacle-class", "obstacle"))}
    mix = dict(MIX, cameras=6, camera_hz=12, objects_per_tick_mean=6, track_mean_ticks=24)
    ticks, submits, needed = track_schedule(mix, refs, 20.0, 2**33 + 1)
    assert len(ticks) == 1440 and needed == 96
    frames = sum(len(s.slots) for s in submits)
    fresh = sum(int(s.fresh.sum()) for s in submits)
    assert frames == 2 * 8640
    # a track lasts about 24 ticks of its camera, less where its slot empties
    assert 8 < frames / fresh < 24
    for s in submits:
        assert len(set(s.slots.tolist())) == len(s.slots)   # one frame a track a tick
    again = track_schedule(mix, refs, 20.0, 2**33 + 1)[1]
    assert all((a.slots == b.slots).all() and (a.fresh == b.fresh).all()
               for a, b in zip(submits, again))


def test_the_network_under_a_prior_is_the_file_with_that_root_row():
    doc = json.loads((ROOT / "chipbench/networks/obstacle-class.json").read_text())
    ref = Tracked(doc, "obstacle", 0.95)
    np.testing.assert_allclose(transition(4, 0.95).sum(axis=1), 1.0)
    thr = ref.thresholds(ref.predict([0.7, 0.1, 0.1, 0.1]))
    under = ref.under(thr)
    bounds = (256,) + thr + (0,)
    assert under.rows[0][0].tolist() == [(bounds[v] - bounds[v + 1]) / 256 for v in range(4)]
    # the published prior gives the network file itself
    same = ref.under(ref.thresholds(ref.published))
    np.testing.assert_array_equal(same.p_e, Network(doc).p_e)
    # clamped to a class, the root is that class
    assert ref.clamped(2).rows[0][0].tolist() == [0.0, 0.0, 1.0, 0.0]
