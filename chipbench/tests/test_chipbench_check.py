"""The reference, and the comparison that decides ``correct``, on synthetic outputs."""

import numpy as np
import pytest

from chipbench import check, program
from chipbench.reference import NETWORKS, Network, dac_row

NAMES = sorted(p.stem for p in NETWORKS.glob("*.json"))
N_BITS = 4096


def test_network_files_are_the_scenario_library():
    from repro.bayesnet import SCENARIOS, by_name

    assert NAMES == sorted(SCENARIOS)
    for name in NAMES:
        import json

        with open(NETWORKS / f"{name}.json") as f:
            ours = program.spec(json.load(f))
        lib = by_name(name)
        assert ours.evidence == lib.evidence and ours.queries == lib.queries
        for a, b in zip(ours.nodes, lib.nodes):
            assert (a.name, a.parents, a.k) == (b.name, b.parents, b.k)
            assert a.value_probs() == b.value_probs()


@pytest.mark.parametrize("name", NAMES)
def test_reference_matches_the_programs_oracle(name):
    from repro.bayesnet import by_name, make_posterior_fn

    net = Network.load(name)
    ev = net.pattern_rows(np.arange(net.p_e.size))
    exact, p_e = make_posterior_fn(by_name(name), dac_quantize=True)(ev)
    np.testing.assert_allclose(net.p_e, np.asarray(p_e), rtol=2e-5, atol=1e-9)
    live = net.p_e > 1e-6
    ours = net.post if not net.binary_layout else net.post[:, :, 1]
    np.testing.assert_allclose(ours[live], np.asarray(exact)[live], atol=5e-5)


def test_dac_rows():
    assert dac_row((0.8, 0.2)).tolist() == [205 / 256, 51 / 256]
    assert dac_row((0.55, 0.18, 0.17, 0.1)).sum() == 1.0


def sound_outputs(net, rng, frames, n_bits):
    """What a sound program returns: binomial accepted counts and sampled posteriors."""
    ev = net.sample_rows(rng, frames)
    pat = net.pattern_index(ev)
    acc = rng.binomial(n_bits, net.p_e[pat])
    shape = (frames, len(net.queries)) if net.binary_layout else (frames, len(net.queries), net.kmax)
    post = np.zeros(shape)
    for qi, c in enumerate(net.q_cards):
        p = net.post[pat, qi, :c]
        counts = np.stack([rng.multinomial(a, pp / pp.sum()) for a, pp in zip(acc, p)])
        frac = np.divide(counts, acc[:, None], out=np.zeros(counts.shape), where=acc[:, None] > 0)
        if net.binary_layout:
            post[:, qi] = frac[:, 1]
        else:
            post[:, qi, :c] = frac
    return ev, post, acc, net.argmax(post)


def readings(n_bits_program, alter=None, seed=0, frames=3000):
    rng = np.random.default_rng(seed)
    comp = check.Comparison(N_BITS)
    for name in NAMES:
        net = Network.load(name)
        ev, post, acc, dec = sound_outputs(net, rng, frames, n_bits_program)
        if alter is not None:
            alter(net, post, acc, dec)
        comp.add(net, ev, post, acc, dec)
    return comp.readings()


LIMITS = check.load_limits()


def test_sound_outputs_are_correct():
    r = readings(N_BITS)
    assert r["acc_z2"] == pytest.approx(1.0, abs=0.1)
    assert r["post_z2"] == pytest.approx(1.0, abs=0.1)
    assert check.judge(r, LIMITS)[0]


def test_a_quarter_of_the_bits_is_not_correct():
    r = readings(N_BITS // 4)
    assert r["acc_z2"] > 100
    assert r["post_z2"] == pytest.approx(4.0, rel=0.15)
    assert not check.judge(r, LIMITS)[0]


def _flip_one(net, post, acc, dec):
    i = int(np.argmax(acc))
    if post.ndim == 2:
        post[i, 0] = 1.0 - post[i, 0]
    else:
        post[i, 0, :] = post[i, 0, ::-1]


def _flip_decision(net, post, acc, dec):
    dec[0, 0] = 1 - dec[0, 0] if net.q_cards[0] == 2 else (dec[0, 0] + 1) % net.q_cards[0]


@pytest.mark.parametrize("alter,number", [(_flip_one, "post_tail"), (_flip_decision, "decision_mismatch")])
def test_an_altered_answer_is_not_correct(alter, number):
    r = readings(N_BITS, alter=alter)
    ok, shown = check.judge(r, LIMITS)
    assert not ok and shown[number]["value"] > shown[number]["limit"]


def test_missing_frames_and_empty_comparisons_are_not_correct():
    comp = check.Comparison(N_BITS)
    assert not check.judge(comp.readings(), LIMITS)[0]       # nothing compared
    r = readings(N_BITS)
    r["frames_missing"] = 1
    assert not check.judge(r, LIMITS)[0]


def test_decisions_are_judged_by_the_frames_counts():
    """An exact binary tie decides value 0, whatever ulp the float posterior lands on."""
    net = Network.load("pedestrian-night")
    acc = np.array([2468, 2468, 100, 0])
    post = np.array([[np.nextafter(np.float32(0.5), np.float32(1)), 0.75],
                     [0.5, 0.25], [0.51, 0.49], [0.0, 0.0]], np.float32)
    assert net.count_argmax(post, acc).tolist() == [[0, 1], [0, 0], [1, 0], [0, 0]]
    assert net.argmax(post).tolist()[0] == [1, 1]        # the float argmax flips the tie
    comp = check.Comparison(N_BITS)
    ev = net.sample_rows(np.random.default_rng(0), 4)
    comp.add(net, ev, post, acc, net.count_argmax(post, acc))
    assert comp.mismatch == 0 and comp.tie_flips == 1 and comp.tie_flips_at_ties == 1
    assert net.count_tied(post, acc).tolist() == [[True, False], [True, False], [False, False],
                                                  [False, False]]


def test_count_ties_of_a_kary_query():
    net = Network.load("obstacle-class")
    assert not net.binary_layout
    acc = np.array([10, 10])
    post = np.zeros((2, len(net.queries), net.kmax), np.float32)
    post[0, 0, :4] = [0.4, 0.4, 0.2, 0.0]       # values 0 and 1 tie
    post[1, 0, :4] = [0.1, 0.5, 0.3, 0.1]
    assert net.count_tied(post, acc)[:, 0].tolist() == [True, False]
    assert net.count_argmax(post, acc)[:, 0].tolist() == [0, 1]


def test_judge_shows_every_number_beside_its_limit():
    r = {"acc_z2": 1.0, "post_z2": float("nan"), "post_tail": 0.5, "decision_mismatch": 0,
         "frames_missing": 0}
    ok, shown = check.judge(r, LIMITS)
    assert not ok                                 # nothing compared for post_z2
    assert list(shown) == list(LIMITS)
    assert shown["post_z2"] == {"value": None, "limit": LIMITS["post_z2"]}
    assert shown["acc_z2"] == {"value": 1.0, "limit": LIMITS["acc_z2"]}
