"""Tracked networks: a root's belief carried from frame to frame on the device.

* the spec's ``state`` is validated, and an untracked network lowers to the
  same program as before it existed (its lowered text is pinned);
* the Pallas kernel and the jnp reference give bit-identical counts with
  per-frame state thresholds;
* the tracked program agrees with :func:`filter_posteriors` frame by frame
  (accepted counts and posteriors within their binomial spread), and a
  whole track's final belief lies within the spread of a sound noisy
  filter around the exact one;
* the table rules: fresh tracks start from the published prior, an empty
  sample keeps its prediction, padding writes nothing.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bayesnet import (
    SCENARIOS,
    by_name,
    compile_network,
    filter_posteriors,
    make_posterior_fn,
    sample_evidence,
    sweep_plan,
)
from repro.bayesnet.spec import NetworkSpec, Node
from repro.core import rng
from repro.kernels.net_sweep import net_sweep


def stay(k, p=0.9):
    return [[p if i == j else (1 - p) / (k - 1) for j in range(k)] for i in range(k)]


def tracked(name, root, p=0.9):
    base = by_name(name)
    return dataclasses.replace(base, state=(root, stay(base.card(root), p)))


# --- the spec ---------------------------------------------------------------------


@pytest.mark.parametrize("state,match", [
    (("nope", stay(2)), "unknown state node"),
    (("brake", stay(2)), "not a root"),
    (("night", stay(2)), "not a query"),
    (("pedestrian", stay(3)), "2 x 2 transition"),
    (("pedestrian", [[0.5, 0.4], [0.1, 0.9]]), "not a distribution"),
    ("pedestrian", "root name, transition rows"),
])
def test_a_bad_state_is_refused(state, match):
    base = by_name("pedestrian-night")
    with pytest.raises(ValueError, match=match):
        NetworkSpec(base.name, base.nodes, base.evidence, base.queries, state=state)


def test_a_tracked_spec_names_its_root_and_transition():
    spec = tracked("obstacle-class", "obstacle")
    assert spec.state_node == "obstacle" and len(spec.transition) == 4
    assert by_name("obstacle-class").state_node is None
    plan = sweep_plan(spec, spec.queries, spec.evidence)
    assert plan.n_state_cols == 3 and plan.nodes[plan.state][0] == ()
    assert sweep_plan(by_name("obstacle-class"), spec.queries, spec.evidence).state is None


def test_a_tracked_network_needs_the_fused_single_device_program():
    spec = tracked("pedestrian-night", "pedestrian")
    with pytest.raises(ValueError, match="fused"):
        compile_network(spec, n_bits=128, fused=False)
    with pytest.raises(ValueError, match="must be a query"):
        compile_network(spec, n_bits=128, queries=("brake",))
    net = compile_network(spec, n_bits=128)
    ev = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="needs table"):
        net.run(jax.random.PRNGKey(0), ev)
    with pytest.raises(ValueError, match="one entry per frame"):
        net.run(jax.random.PRNGKey(0), ev, net.new_table(4), [0], [True])
    with pytest.raises(ValueError, match="untracked"):
        compile_network(by_name("pedestrian-night"), n_bits=128).run(
            jax.random.PRNGKey(0), ev, net.new_table(4), [0, 1], [True, True])


# --- untracked programs are unchanged ---------------------------------------------

# sha256 (first 16 hex digits) of the lowered text of each scenario's programs
# at 8 frames x 256 bits, taken from the tree before tracked networks existed
_LOWERED = {
    "intersection": {"run": "8b2495352ce89b8a", "decide": "2e30293135ed757c",
                     "sweep_k0": "b5bed9d00d3a83ae", "sweep_k1": "c3e8b966584654f6"},
    "intersection-cat": {"run": "1575df57f715f0b1", "decide": "5f1417a1564ae916",
                         "sweep_k0": "24d49e726340a094", "sweep_k1": "3260289a389e72cd"},
    "lane-change": {"run": "07cf1c2dc286263b", "decide": "c262ef1bdfb5dbef",
                    "sweep_k0": "e35d5b16be937823", "sweep_k1": "40088b3cc6234c13"},
    "obstacle-class": {"run": "7e8460ee68c70a5b", "decide": "4647a2a1ac381617",
                       "sweep_k0": "12b3495059399156", "sweep_k1": "2885ffdfc44318ec"},
    "obstacle-detection": {"run": "618b96071c5aec54", "decide": "b2c8c7b307d6e97b",
                           "sweep_k0": "239afd667d0d6204", "sweep_k1": "8debfa60b45dacda"},
    "pedestrian-night": {"run": "56b1c0fa6d5eff83", "decide": "8e8950b5a10a3bba",
                         "sweep_k0": "a9d695dcba80c2e4", "sweep_k1": "ee3490a7bb3cedde"},
    "sensor-degradation": {"run": "5eff9f6055bac598", "decide": "a0e475b637cdbd5e",
                           "sweep_k0": "cd61be25c70eaf9f", "sweep_k1": "8193945dcc2fa0d4"},
}


def _digest(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_an_untracked_network_lowers_as_before(name):
    spec = by_name(name)
    ev = jnp.zeros((8, len(spec.evidence)), jnp.int32)
    key = jax.random.PRNGKey(0)
    net = compile_network(spec, n_bits=256)
    plan = sweep_plan(spec, spec.queries, spec.evidence)
    got = {
        "run": _digest(net._run.lower(key, ev)),
        "decide": _digest(net._decide.lower(key, ev)),
        "sweep_k0": _digest(net_sweep.lower(key, ev, plan=plan, n_bits=256, use_kernel=False,
                                            interpret=True)),
        "sweep_k1": _digest(net_sweep.lower(key, ev, plan=plan, n_bits=256, use_kernel=True,
                                            interpret=True)),
    }
    assert got == _LOWERED[name]


# --- kernel and reference ---------------------------------------------------------


@pytest.mark.parametrize("name,root", [("obstacle-class", "obstacle"),
                                       ("pedestrian-night", "pedestrian")])
def test_kernel_and_reference_counts_are_bit_identical_with_state(name, root):
    spec = tracked(name, root)
    plan = sweep_plan(spec, spec.queries, spec.evidence)
    r = np.random.default_rng(5)
    b = 16
    ev = np.stack([r.integers(0, spec.card(e), b) for e in spec.evidence], axis=-1)
    # per-frame thresholds: random non-increasing DAC rows, 0 and 256 included
    thr = -np.sort(-r.integers(0, 257, (b, plan.n_state_cols)), axis=-1)
    thr[0], thr[1] = 0, 256
    cols = jnp.asarray(np.concatenate([ev, thr], axis=1), jnp.int32)
    key = jax.random.PRNGKey(11)
    ref = net_sweep(key, cols, plan=plan, n_bits=512, use_kernel=False, decide=True)
    ker = net_sweep(key, cols, plan=plan, n_bits=512, use_kernel=True, interpret=True,
                    decide=True)
    for a, k in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(k))
    # a frame whose thresholds are its CPT row's draws exactly the untracked sweep
    static = jnp.asarray(np.broadcast_to(plan.nodes[plan.state][2][0], thr.shape), jnp.int32)
    same = net_sweep(key, jnp.concatenate([jnp.asarray(ev, jnp.int32), static], axis=1),
                     plan=plan, n_bits=512, use_kernel=False)
    plain = sweep_plan(by_name(name), spec.queries, spec.evidence)
    base = net_sweep(key, jnp.asarray(ev, jnp.int32), plan=plain, n_bits=512, use_kernel=False)
    for a, c in zip(same, base):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_traced_dac_matches_the_trace_time_dac():
    r = np.random.default_rng(2)
    for k in (2, 3, 4, 5):
        probs = r.dirichlet(np.full(k, 0.7), 200).astype(np.float32)
        got = np.asarray(rng.cdf_thresholds(jnp.asarray(probs)))
        want = np.asarray([rng.cdf_thresholds_int(tuple(float(x) for x in p)) for p in probs])
        # float32 tails summed in another order may land a half-step apart
        assert np.mean(got == want) > 0.99 and np.abs(got - want).max() <= 1


# --- the program against the exact filter -----------------------------------------


def random_tracked_spec(seed, k, n_nodes):
    """A seeded DAG: the tracked root (k values), then binary/ternary nodes whose
    parents are the root and earlier nodes; evidence = the non-root leaves' half."""
    r = np.random.default_rng(seed)
    nodes = [Node.categorical("s", (), [r.dirichlet(np.full(k, 3.0))])]
    cards = {"s": k}
    for i in range(1, n_nodes):
        c = int(r.integers(2, 4))
        pool = [n.name for n in nodes]
        parents = ["s"] + [p for p in pool[1:] if r.random() < 0.3][:1]
        n_rows = int(np.prod([cards[p] for p in parents]))
        rows = r.dirichlet(np.full(c, 0.8), n_rows) * 0.9 + 0.1 / c
        nodes.append(Node.categorical(f"x{i}", parents, rows))
        cards[f"x{i}"] = c
    names = [n.name for n in nodes[1:]]
    evidence = tuple(names[: max(1, (len(names) + 1) // 2)])
    trans = r.dirichlet(np.full(k, 0.5), k) * 0.5 + 0.5 * np.eye(k)
    trans = trans * 0.94 + 0.06 / k
    return NetworkSpec(f"rand{seed}", tuple(nodes), evidence, ("s",) + tuple(names[-1:]),
                       state=("s", trans.tolist()))


def run_tracks(spec, n_bits, frames_per_track=40, n_tracks=6, seed=0):
    """Interleaved tracks through the program: each launch holds a random subset
    of the tracks, one frame each; track 0 restarts fresh half-way."""
    r = np.random.default_rng(seed)
    net = compile_network(spec, n_bits=n_bits)
    table = net.new_table(n_tracks + 2)
    seen = np.zeros(n_tracks, int)
    ev_all, tid_all, fresh_all, post_all, acc_all = [], [], [], [], []
    launch = 0
    pool = np.asarray(sample_evidence(spec, jax.random.PRNGKey(seed), frames_per_track * n_tracks))
    used = 0
    while seen.min() < frames_per_track:
        live = np.flatnonzero(seen < frames_per_track)
        pick = live[r.random(len(live)) < 0.6]
        if not len(pick):
            continue
        fresh = (seen[pick] == 0) | ((pick == 0) & (seen[pick] == frames_per_track // 2))
        ev = pool[used : used + len(pick)]
        used += len(pick)
        idx = pick + 1                    # rows 0 and n_tracks + 1 stay untouched
        post, acc, table = net.run(jax.random.PRNGKey(1000 + launch), ev, table, idx, fresh)
        launch += 1
        ev_all.append(ev)
        tid_all.append(idx)
        fresh_all.append(fresh)
        post_all.append(np.asarray(post))
        acc_all.append(np.asarray(acc))
        seen[pick] += 1
    cat = np.concatenate
    return (net, np.asarray(table), cat(ev_all), cat(tid_all), cat(fresh_all), cat(post_all),
            cat(acc_all))


def z_scores(spec, n_bits, ev, tid, fresh, post, acc):
    ref, p_e, _ = filter_posteriors(spec, ev, tid, fresh, post, acc)
    m = n_bits * p_e
    var = n_bits * p_e * (1 - p_e)
    acc_z2 = np.mean((acc - m) ** 2 / var)
    got = post.reshape(len(post), -1)
    want = ref.reshape(len(ref), -1)
    mm = np.broadcast_to(m[:, None], got.shape)
    pq = want * (1 - want)
    ok = (mm * pq >= 5) & (acc[:, None] > 0)
    post_z2 = np.sum((got - want)[ok] ** 2 * mm[ok] / pq[ok]) / ok.sum()
    return acc_z2, post_z2, ok.sum()


@pytest.mark.parametrize("k,n_nodes,seed", [(2, 3, 1), (2, 5, 2), (4, 4, 3), (4, 6, 4)])
def test_the_tracked_program_follows_the_exact_filter_frame_by_frame(k, n_nodes, seed):
    spec = random_tracked_spec(seed, k, n_nodes)
    net, _, ev, tid, fresh, post, acc = run_tracks(spec, 2048, seed=seed)
    assert fresh.sum() == 6 + 1 and len(ev) == 240
    acc_z2, post_z2, n = z_scores(spec, 2048, ev, tid, fresh, post, acc)
    # means of about 240 (and n) chi-square(1) draws: sound reads 1 +- 0.2
    assert 0.6 < acc_z2 < 1.6, acc_z2
    assert n > 100 and 0.6 < post_z2 < 1.6, (post_z2, n)


@pytest.mark.parametrize("k,n_nodes,seed", [(2, 4, 5), (4, 5, 6)])
def test_a_track_s_final_belief_lies_within_the_noisy_filter_s_spread(k, n_nodes, seed):
    """The last belief of each track against the exact filter (no answers fed
    back), within a tolerance from a Monte-Carlo of a sound noisy filter: each
    step draws Binomial(n, P(e)) accepted samples and a multinomial split of
    them over the state, exactly the sampling the program does."""
    n_bits = 2048
    spec = random_tracked_spec(seed, k, n_nodes)
    net, table, ev, tid, fresh, post, acc = run_tracks(spec, n_bits, seed=seed)
    exact, _, _ = filter_posteriors(spec, ev, tid, fresh)
    r = np.random.default_rng(seed + 100)
    reps = 300
    root = spec.state_node
    trans = np.asarray(spec.transition)
    # P(e | root = s) for each frame, from the exact oracle with the root clamped
    lik = np.stack([
        np.asarray(make_posterior_fn(
            dataclasses.replace(spec, state=None, nodes=tuple(
                Node.categorical(n.name, n.parents, [np.eye(k)[s]]) if n.name == root else n
                for n in spec.nodes)),
            dac_quantize=True)(jnp.asarray(ev))[1], np.float64)
        for s in range(k)], axis=-1)                                   # (F, k)
    published = np.asarray(spec.cpt_rows(root)[0])
    belief = {}
    last = {}
    for f in range(len(ev)):
        t = int(tid[f])
        pred = (np.broadcast_to(published, (reps, k)) if fresh[f]
                else belief[t] @ trans)
        thr = np.asarray([rng.cdf_thresholds_int(tuple(p)) for p in pred])
        bounds = np.concatenate([np.full((reps, 1), 256), thr, np.zeros((reps, 1))], axis=1)
        prior = (bounds[:, :-1] - bounds[:, 1:]) / 256.0
        joint = prior * lik[f]
        p_e = joint.sum(axis=1)
        a = r.binomial(n_bits, np.clip(p_e, 0, 1))
        counts = np.stack([r.multinomial(n, p / p.sum()) if n else np.zeros(k, int)
                           for n, p in zip(a, np.where(p_e[:, None] > 0, joint, 1.0))])
        belief[t] = np.where(a[:, None] > 0, counts / np.maximum(a, 1)[:, None], pred)
        last[t] = f
    state_q = spec.queries.index(root)
    for t, f in last.items():
        want = exact[f, state_q, :k] if exact.ndim == 3 else np.asarray(
            [1 - exact[f, state_q], exact[f, state_q]])
        rms = np.sqrt(np.mean((belief[t] - want) ** 2, axis=0))
        # 6 root-mean-square deviations of the sound filter, and a DAC step
        assert np.all(np.abs(table[t] - want) <= 6 * rms + 1 / 256), (t, table[t], want, rms)
    # rows no frame touched stay as they were
    assert not table[0].any() and not table[-1].any()


# --- the table rules ----------------------------------------------------------------


def test_fresh_tracks_start_from_the_published_prior_and_padding_writes_nothing():
    spec = tracked("pedestrian-night", "pedestrian")
    net = compile_network(spec, n_bits=1024)
    plain = compile_network(by_name("pedestrian-night"), n_bits=1024)
    ev = jnp.asarray([[1, 0, 0], [0, 1, 1], [1, 1, 0], [1, 1, 0]], jnp.int32)
    key = jax.random.PRNGKey(4)
    table = net.new_table(3)
    # frames 0-2 fresh on rows 0-2; the 4th lane is padding (a row past the table)
    post, acc, table = net.run(key, ev, table, [0, 1, 2, 3], [True] * 4)
    want_post, want_acc = plain.run(key, ev)
    np.testing.assert_array_equal(np.asarray(post), np.asarray(want_post))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want_acc))
    p = np.asarray(post)[:3, 0]
    np.testing.assert_allclose(np.asarray(table), np.stack([1 - p, p], axis=-1), atol=1e-6)


def test_an_empty_sample_keeps_the_prediction():
    spec = tracked("obstacle-class", "obstacle", p=0.8)
    net = compile_network(spec, n_bits=32)
    table = jnp.asarray([[0.0, 1.0, 0.0, 0.0]], jnp.float32)
    # the rarest evidence of the network at 32 bits: no sample is accepted
    _, p_e = make_posterior_fn(by_name("obstacle-class"), dac_quantize=True)(
        jnp.asarray(np.indices((2, 4, 3, 3)).reshape(4, -1).T, jnp.int32))
    worst = np.indices((2, 4, 3, 3)).reshape(4, -1).T[int(np.argmin(np.asarray(p_e)))]
    post, acc, table = net.run(jax.random.PRNGKey(0), worst[None], table, [0], [False])
    assert int(acc[0]) == 0 and not np.asarray(post).any()
    np.testing.assert_allclose(np.asarray(table)[0], np.asarray(stay(4, 0.8))[1], rtol=1e-6)
    ref, _, prior = filter_posteriors(spec, np.stack([worst, worst]), [0, 0], [True, False],
                                      np.zeros((2, 2, 4)), [0, 0])
    # the second frame's prior: the first frame's prediction moved once more
    want = np.asarray(spec.cpt_rows("obstacle")[0]) @ np.asarray(stay(4, 0.8))
    bounds = (256,) + rng.cdf_thresholds_int(tuple(want)) + (0,)
    np.testing.assert_allclose(prior[1], [(bounds[v] - bounds[v + 1]) / 256 for v in range(4)])


@pytest.mark.parametrize("as_host", [True, False])
@pytest.mark.parametrize("is_tracked", [True, False], ids=["tracked", "untracked"])
def test_a_run_starts_its_two_host_copies_and_traces_the_same(is_tracked, as_host):
    from repro.obs import Tracer

    spec = tracked("obstacle-class", "obstacle") if is_tracked else by_name("obstacle-class")
    tr = Tracer()
    plain = compile_network(spec, n_bits=256)
    traced = compile_network(spec, n_bits=256, trace=tr)
    ev = np.asarray(sample_evidence(by_name("obstacle-class"), jax.random.PRNGKey(1), 6))
    idx, fresh = np.arange(6) % 3, np.arange(6) < 3
    if not as_host:
        ev, idx, fresh = jnp.asarray(ev), jnp.asarray(idx), jnp.asarray(fresh)
    key = jax.random.PRNGKey(2)
    tables = (plain.new_table(4), traced.new_table(4)) if is_tracked else (None, None)
    for calls, s in ((1, slice(0, 3)), (2, slice(3, None))):
        tracks = [(t, idx[s], fresh[s]) if is_tracked else () for t in tables]
        # the jitted program called directly on device inputs
        ref = plain._run(key, jnp.asarray(ev[s], jnp.int32), *(
            (jnp.array(tables[0]), jnp.asarray(idx[s], jnp.int32), jnp.asarray(fresh[s]))
            if is_tracked else ()))
        want = plain.run(key, ev[s], *tracks[0])
        got = traced.run(key, ev[s], *tracks[1])
        assert len(got) == len(ref) == (3 if is_tracked else 2)
        for r, a, b in zip(ref, want, got):
            assert isinstance(a, jax.Array) and isinstance(b, jax.Array)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
            np.testing.assert_array_equal(np.asarray(b), np.asarray(r))
        assert tr.count("run.host_copies") == 2 * calls
        # the next launch carries the rows this one wrote, traced or not
        if is_tracked:
            tables = (want[2], got[2])
    assert tr.count("decide.host_copies") == 0
