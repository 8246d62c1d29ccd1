"""Whole runs on the CPU at a small size: the chip check skipped, the rest as on the chip.

A sound program is ``correct``; the control (a quarter of the stated
``n_bits``) is not, and neither is a run with the timed path broken
underneath: half of each batch left out, the exchange between chips left
out, an answer altered where it is produced, answers lost.
"""

import io
import json
import shutil
from pathlib import Path

import jax.numpy as jnp
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
CONFIG = {
    "name": "tiny", "tenants": ["pedestrian-night", "obstacle-class"], "n_bits": 512,
    "chips": 1, "reduced": [],
}
MIXES = {
    "tiny-replay": {"mode": "replay", "frames_per_chip": 64, "batches_per_network": 2,
                    "in_flight": 2, "check_frames": 2048},
}


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
            m["workloads"] = ["tiny.tiny-replay"]
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
