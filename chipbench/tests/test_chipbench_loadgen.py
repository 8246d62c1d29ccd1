"""The one traffic generator: seeded, the same work for every seed, rows as each network predicts."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import loadgen
from chipbench.reference import Network

CHIPBENCH = Path(__file__).resolve().parents[1]
NAMES = ["pedestrian-night", "obstacle-class"]


@pytest.fixture(scope="module")
def nets():
    return {t: Network.load(t) for t in NAMES}


@pytest.mark.parametrize("chips", [1, 4])
def test_replay_batches_are_seeded(nets, chips):
    mix = {"frames_per_chip": 64, "batches_per_network": 3}
    a = loadgen.replay_batches(mix, nets, NAMES, chips, 2**33 + 11)
    b = loadgen.replay_batches(mix, nets, NAMES, chips, 2**33 + 11)
    c = loadgen.replay_batches(mix, nets, NAMES, chips, 12)
    assert [x.shape for x in a["obstacle-class"]] == [(64 * chips, 4)] * 3
    assert all(np.array_equal(x, y) for x, y in zip(a["pedestrian-night"], b["pedestrian-night"]))
    assert not np.array_equal(a["pedestrian-night"][0], c["pedestrian-night"][0])
    # every seed offers the same shapes in the same order
    assert {k: [x.shape for x in v] for k, v in a.items()} == \
        {k: [x.shape for x in v] for k, v in c.items()}


def test_rows_follow_the_networks_evidence_distribution(nets):
    mix = {"frames_per_chip": 4096, "batches_per_network": 4}
    batches = loadgen.replay_batches(mix, nets, NAMES, 1, 5)
    for name, net in nets.items():
        pat = net.pattern_index(np.concatenate(batches[name]))
        freq = np.bincount(pat, minlength=net.p_e.size) / len(pat)
        p = net.p_e / net.p_e.sum()
        # within five standard errors of a multinomial share, every pattern
        assert np.all(np.abs(freq - p) <= 5 * np.sqrt(p * (1 - p) / len(pat)) + 1e-12), name


def test_the_committed_mix_names_its_mode_and_sizes():
    mix = json.loads((CHIPBENCH / "traffic" / "replay.json").read_text())
    assert mix["mode"] == "replay"
    for key in ("frames_per_chip", "batches_per_network", "in_flight", "check_frames"):
        assert int(mix[key]) > 0, key
    assert mix["check_frames"] % mix["frames_per_chip"] == 0


@pytest.fixture(scope="module")
def ticks_mix():
    return json.loads((CHIPBENCH / "traffic" / "ticks.json").read_text())


@pytest.fixture(scope="module")
def vehicle_nets(ticks_mix):
    return {t: Network.load(t) for t in ticks_mix["object_networks"] + ticks_mix["scene_networks"]}


def _shape(ticks):
    return [(t.due_s, [(n, rows.shape) for n, rows in t.frames]) for t in ticks]


def test_the_tick_schedule_is_seeded_and_every_seed_gets_the_same_work(ticks_mix, vehicle_nets):
    a = loadgen.tick_schedule(ticks_mix, vehicle_nets, 20.0, 2**33 + 11)
    b = loadgen.tick_schedule(ticks_mix, vehicle_nets, 20.0, 2**33 + 11)
    c = loadgen.tick_schedule(ticks_mix, vehicle_nets, 20.0, 12)
    assert _shape(a) == _shape(b)
    assert all(np.array_equal(x, y) for s, t in zip(a, b)
               for (_, x), (_, y) in zip(s.frames, t.frames))
    assert _shape(a) != _shape(c)                      # another order of the same ticks
    assert sorted(t.objects for t in a) == sorted(t.objects for t in c)
    # 72 ticks a second, the 6 cameras' phases evenly staggered, about 22 frames a tick
    assert len(a) == 1440 and [t.due_s for t in a[:7]] == pytest.approx([i / 72 for i in range(7)])
    frames = [sum(len(rows) for _, rows in t.frames) for t in a]
    assert np.mean(frames) == pytest.approx(22.0, abs=0.1)
    assert all(f == 3 * t.objects + 4 for f, t in zip(frames, a))


def test_objects_a_tick_are_the_poisson_laws_quantiles():
    q = loadgen.poisson_quantiles(6.0, 10000)
    assert np.all(np.diff(q) >= 0) and q.mean() == pytest.approx(6.0, abs=0.01)
    assert q.var() == pytest.approx(6.0, rel=0.02)
    assert loadgen.poisson_quantiles(6.0, 0).size == 0


def test_the_committed_ticks_mix_names_its_mode_and_networks(ticks_mix):
    config = json.loads((CHIPBENCH / "configs" / "vehicle-nuscenes.json").read_text())
    assert ticks_mix["mode"] == "served"
    assert set(ticks_mix["object_networks"] + ticks_mix["scene_networks"]) == set(config["tenants"])
    assert ticks_mix["cameras"] * ticks_mix["camera_hz"] == 72
    assert config["deadline_ms"] == 83 and config["max_batch"] == 32
