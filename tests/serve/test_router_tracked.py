"""Tracked tenants through FrameDriver and BayesRouter.

The driver owns the belief table and never puts two frames of one track in a
launch (the later one waits, counted in ``track.deferred``); the router
carries track ids through in submission order; untracked tenants keep
their calls and results.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.bayesnet import FrameDriver, by_name, compile_network, sample_evidence
from repro.obs import MetricsRegistry, Tracer
from repro.serve.router import BayesRouter, RouterPolicy, tenant_salt

STAY = [[0.9, 0.1], [0.1, 0.9]]


def spec():
    return dataclasses.replace(by_name("pedestrian-night"), state=("pedestrian", STAY))


def rows(n, seed=0):
    return np.asarray(sample_evidence(spec(), jax.random.PRNGKey(seed), n))


def test_a_launch_holds_one_frame_a_track_and_the_rest_wait_in_order():
    tr, mx = Tracer(), MetricsRegistry()
    d = FrameDriver(compile_network(spec(), n_bits=256), max_batch=8, max_tracks=4,
                    base_key=jax.random.PRNGKey(1), salt=3, trace=tr, metrics=mx)
    tracks = [0, 1, 0, 2, 0, 3, 1]
    launches = []
    run = d.net.run

    def spy(key, ev, table, idx, fresh):
        launches.append(np.asarray(idx)[: len(ev)].tolist())
        return run(key, ev, table, idx, fresh)

    object.__setattr__(d.net, "run", spy)
    rids = d.submit(rows(7), tracks=tracks, fresh=[True, True, False, True, False, True, False])
    out = d.drain()
    assert sorted(out) == rids
    real = [[t for t in launch if t < 4] for launch in launches]
    assert all(len(set(ts)) == len(ts) for ts in real)
    assert real[0] == [0, 1, 2, 3] and real[1] == [0, 1] and real[2] == [0]
    # deferrals: frames 2, 4, 6 at the first launch, frame 4 at the second
    assert mx.count("track.deferred") == 4
    assert mx.count("track.fresh") == 4 and mx.count("track.carried") == 3
    assert mx.count("track.held") == 0
    packs = {s.span_id for s in tr.spans if s.name == "pack"}
    index = [s for s in tr.spans if s.name == "track.index"]
    assert len(index) == 3 and all(s.parent_id in packs for s in index)


def test_a_tracked_driver_matches_the_program_called_in_order():
    net = compile_network(spec(), n_bits=512)
    d = FrameDriver(net, max_batch=4, max_tracks=2, base_key=jax.random.PRNGKey(2), salt=5)
    ev = rows(6, seed=4)
    d.submit(ev[:3], tracks=[0, 1, 0], fresh=[True, True, False])
    d.submit(ev[3:], tracks=[1, 0, 1], fresh=[False, False, True])
    got = d.drain()
    # the same launches by hand: [0, 1], [0, 1], [0, 1]  (one frame a track each)
    key = jax.random.fold_in(jax.random.PRNGKey(2), 5)
    table = net.new_table(2)
    want = {}
    for n, (rid_a, rid_b, fa, fb) in enumerate([(0, 1, True, True), (2, 3, False, False),
                                                (4, 5, False, True)]):
        post, acc, table = net.run(jax.random.fold_in(key, n), ev[[rid_a, rid_b]], table,
                                   [0, 1], [fa, fb])
        want[rid_a], want[rid_b] = (post[0], acc[0]), (post[1], acc[1])
    for rid, (post, acc) in want.items():
        np.testing.assert_array_equal(got[rid][0], np.asarray(post))
        assert got[rid][1] == int(acc)
    np.testing.assert_array_equal(np.asarray(d.tracks), np.asarray(table))
    d.reset_tracks()
    assert not np.asarray(d.tracks).any()


def test_a_tracked_driver_refuses_what_it_cannot_carry():
    net = compile_network(spec(), n_bits=128)
    with pytest.raises(ValueError, match="max_tracks"):
        FrameDriver(net)
    d = FrameDriver(net, max_tracks=2)
    with pytest.raises(ValueError, match="tracks and fresh"):
        d.submit(rows(1))
    with pytest.raises(ValueError, match=r"\[0, 2\)"):
        d.submit(rows(1), tracks=[2], fresh=[True])
    plain = FrameDriver(compile_network(by_name("pedestrian-night"), n_bits=128))
    with pytest.raises(ValueError, match="untracked"):
        plain.submit(rows(1), tracks=[0], fresh=[True])
    with pytest.raises(ValueError, match="untracked"):
        FrameDriver(plain.net, max_tracks=4)
    with pytest.raises(ValueError, match="tracked state"):
        d.swap_net(plain.net)


def test_the_router_carries_track_ids_and_serves_tracked_frames_in_order():
    mx = MetricsRegistry()
    router = BayesRouter(RouterPolicy(max_degrade=2, capacity=1), base_key=jax.random.PRNGKey(7),
                         n_bits=256, max_batch=8, metrics=mx)
    name = router.register(spec(), max_tracks=4)
    ev = rows(8, seed=9)
    # later frames of a track carry earlier deadlines: admission still keeps submission order
    a = router.submit(name, ev[:4], deadline_ms=5e3, track_ids=[0, 1, 2, 3], fresh=[True] * 4)
    b = router.submit(name, ev[4:], deadline_ms=4e3, track_ids=[0, 1, 2, 3], fresh=[False] * 4)
    res = router.drain()
    assert sorted(res) == a + b and {r.status for r in res.values()} == {"OK"}
    # the queue is deep enough to degrade, but a tracked tenant serves at level 0
    assert {r.degrade_level for r in res.values()} == {0}
    t = router.tenant(name)
    assert list(t.drivers) == [0]
    assert mx.count("track.fresh") == 4 and mx.count("track.carried") == 4
    # the standalone driver with the tenant's salt: same answers, frame for frame
    d = FrameDriver(compile_network(spec(), n_bits=256), max_batch=8, max_tracks=4,
                    base_key=jax.random.PRNGKey(7), salt=tenant_salt(name))
    d.submit(ev, tracks=[0, 1, 2, 3] * 2, fresh=[True] * 4 + [False] * 4)
    alone = d.drain()
    for i, rid in enumerate(a + b):
        np.testing.assert_array_equal(res[rid].post, alone[i][0])
    np.testing.assert_array_equal(np.asarray(t.drivers[0].tracks), np.asarray(d.tracks))


def test_the_router_refuses_track_ids_where_they_do_not_belong():
    router = BayesRouter(n_bits=128, max_batch=4)
    with pytest.raises(ValueError, match="max_tracks"):
        router.register(spec())
    with pytest.raises(ValueError, match="max_tracks"):
        router.register("pedestrian-night", max_tracks=4)
    router.register(spec(), max_tracks=4)
    with pytest.raises(ValueError, match="track_ids and fresh"):
        router.submit("pedestrian-night", rows(1))
    with pytest.raises(ValueError, match=r"\[0, 4\)"):
        router.submit("pedestrian-night", rows(1), track_ids=[4], fresh=[True])
    other = BayesRouter(n_bits=128, max_batch=4)
    with pytest.raises(ValueError, match="untracked"):
        other.submit("pedestrian-night", rows(1), track_ids=[0], fresh=[True])
    # an untracked tenant's calls and results are as they were
    rid = other.submit("lane-change", np.zeros((1, len(by_name("lane-change").evidence)), int))
    assert other.drain()[rid[0]].status == "OK"
