"""The one traffic generator: seeded, the same shapes for every seed, rows as each network predicts."""

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
