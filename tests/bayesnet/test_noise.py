"""Crossbar NoiseModel correctness.

Three pillars:

* **noise=None regression**: compiling WITHOUT a noise model must stay
  BIT-identical to the pre-noise compiler -- full decide() outputs of all 7
  scenarios (and unfused run() for two) are pinned against goldens captured
  from the pre-noise tree (commit 5d45000).
* **perturbation mechanics**: perturbed rows are valid CDF rows, a pure
  function of (seed, cycle, node name), cycle re-draws only read noise,
  ``scaled(0)`` is the exact identity, stuck-at extremes pin to 0/256, and
  the default magnitudes are tied to the paper-calibrated device model.
* **noisy agreement**: under the nominal model, fused and unfused programs
  match the *perturbed-CPT* enumeration oracle within stochastic noise --
  the oracle twin keeps ground truth exact under any noise level.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.bayesnet import (
    NoiseModel,
    SCENARIOS,
    by_name,
    compile_network,
    make_posterior_fn,
    perturbed_cdf_rows,
    sample_evidence,
)
from repro.core import rng
from repro.core.device import DEFAULT_PARAMS

# --- noise=None regression: bit-identical to the pre-noise compiler ----------------

# Goldens captured from the pre-noise tree (commit 5d45000): per scenario,
# evidence = sample_evidence(spec, PRNGKey(3), 8), fused decide with
# PRNGKey(0) at n_bits=1024.  float32 posteriors as uint32 bit patterns.
_GOLD_FUSED = {
    "intersection": {
        "post_bits": [[1057609886, 0, 1047285445], [1017406289, 0, 1058451552],
                      [1029434210, 1015640861, 1058540991], [1018974820, 0, 1058796603],
                      [1058642330, 0, 1053609165], [1052490684, 0, 1053609165],
                      [1006124560, 997735952, 1058426259], [1029990088, 1016611973, 1057681850]],
        "dec": [[1, 0, 0], [0, 0, 1], [0, 0, 1], [0, 0, 1],
                [1, 0, 0], [0, 0, 0], [0, 0, 1], [0, 0, 1]],
        "acc": [13, 299, 298, 261, 5, 30, 264, 269],
    },
    "intersection-cat": {
        "post_bits": [
            [[1017759818, 1014934639, 1064744716], [1065092430, 1014934639, 0], [1042577928, 1062658430, 0]],
            [[1063828015, 1021274894, 1031951304], [1062302813, 1044000396, 0], [1061845253, 1045830637, 0]],
            [[1022901776, 1024782857, 1064234735], [1064743135, 1024782857, 0], [1046847438, 1061591052, 0]],
            [[0, 1065353216, 0], [1065353216, 0, 0], [1065353216, 0, 0]],
            [[1042983595, 1051372203, 1056964608], [1062557013, 1042983595, 0], [1059760811, 1051372203, 0]],
            [[1023822730, 1009979235, 1064619786], [1065169858, 1009979235, 0], [1046834103, 1061594386, 0]],
            [[1030811889, 0, 1064366321], [1065353216, 0, 0], [1059431846, 1052030133, 0]],
            [[1042536202, 1056293519, 1052266988], [1065017672, 1017370378, 0], [1056964608, 1056964608, 0]]],
        "dec": [[2, 0, 1], [0, 0, 0], [2, 0, 1], [1, 0, 0],
                [2, 0, 0], [2, 0, 1], [2, 0, 0], [1, 0, 0]],
        "acc": [193, 110, 165, 4, 6, 183, 17, 50],
    },
    "lane-change": {
        "post_bits": [[1002950156, 1064637115, 1063102614], [1019517862, 1052535423, 1054146036],
                      [1008422000, 1064081010, 1062808804], [1048576000, 1048576000, 1040187392],
                      [1052490684, 1059760811, 1047457519], [1014763457, 1054383498, 1054899720],
                      [1050863802, 1059252410, 0], [1001590627, 1064436428, 1063061247]],
        "dec": [[0, 1, 1], [0, 0, 0], [0, 1, 1], [0, 0, 0],
                [0, 1, 0], [0, 0, 0], [0, 1, 0], [0, 1, 1]],
        "acc": [164, 125, 211, 16, 30, 130, 22, 183],
    },
    "obstacle-class": {
        "post_bits": [
            [[1065353216, 0, 0, 0], [1065353216, 0, 0, 0]],
            [[0, 0, 1065353216, 0], [1047589105, 1061405636, 0, 0]],
            [[1064996254, 1018055745, 0, 0], [1064782077, 1024159796, 0, 0]],
            [[1064774691, 1024277963, 0, 0], [1063617642, 1037294769, 0, 0]],
            [[1064867925, 1019943809, 998729643, 0], [1064174651, 1032838694, 0, 0]],
            [[1065353216, 0, 0, 0], [1064385300, 1030508229, 0, 0]],
            [[1064011039, 0, 1025758986, 1025758986], [1062668861, 1042536202, 0, 0]],
            [[1064814498, 1018946513, 999706586, 999706586], [1064044901, 1033876696, 0, 0]]],
        "dec": [[0, 0], [2, 1], [0, 0], [0, 0], [0, 0], [0, 0], [0, 0], [0, 0]],
        "acc": [7, 17, 235, 29, 242, 208, 25, 218],
    },
    "obstacle-detection": {
        "post_bits": [
            [[1056964608, 1051372203, 1042983595, 0], [1056964608, 1056964608, 0, 0]],
            [[1023969417, 1040746633, 1059760811, 1042983595], [1052490684, 1059201570, 0, 0]],
            [[1064473512, 1018697475, 1010308867, 1016686722], [1064285004, 1031955874, 0, 0]],
            [[1061997773, 1036831949, 1036831949, 0], [1063675494, 1036831949, 0, 0]],
            [[1064640670, 1016997263, 1006438629, 1014827237], [1063863347, 1035329125, 0, 0]],
            [[1065353216, 0, 0, 0], [1064563700, 1027653825, 0, 0]],
            [[1064774691, 1015889355, 0, 1015889355], [1064485429, 1028906161, 0, 0]],
            [[1064496507, 1015771188, 1011951694, 1018055745], [1063711191, 1036546379, 0, 0]]],
        "dec": [[0, 0], [2, 1], [0, 0], [0, 0], [0, 0], [0, 0], [0, 0], [0, 0]],
        "acc": [6, 30, 267, 10, 259, 170, 58, 235],
    },
    "pedestrian-night": {
        "post_bits": [[1057776409, 1062106013], [1063339950, 1065017672],
                      [1028930141, 1014934639], [1017463209, 1002233171],
                      [1027524041, 1007069627], [1055748868, 1060976551],
                      [1048754481, 1062140558], [1056057731, 1059231799]],
        "dec": [[1, 1], [1, 1], [0, 0], [0, 0], [0, 0], [0, 1], [0, 1], [0, 1]],
        "acc": [62, 50, 386, 347, 365, 69, 47, 74],
    },
    "sensor-degradation": {
        "post_bits": [[1044809686, 1056622216], [1019255317, 1015889355],
                      [1025540199, 1022621279], [1025009864, 1016621256],
                      [1025758986, 1013706234], [1024277963, 1016730845],
                      [1021996516, 1016021799], [1027565281, 1016667930]],
        "dec": [[0, 0], [0, 0], [0, 0], [0, 0], [0, 0], [0, 0], [0, 0], [0, 0]],
        "acc": [98, 638, 638, 645, 625, 638, 629, 642],
    },
}

# Unfused run() goldens, same evidence/keys (one binary + one categorical net),
# pinned as integer counts: ``numer`` holds the per-slot numerator popcounts
# (values 1 .. card-1 per query, query order) and ``acc`` the accepted bits.
# They are the pre-noise tree's float32 goldens times ``acc``: the counts, not
# the float bits of the unfused assembly, are what the sweep must reproduce.
_GOLD_UNFUSED = {
    "pedestrian-night": {
        "numer": [[22, 38], [45, 48], [21, 8], [7, 6], [17, 4], [46, 52], [16, 44],
                  [33, 55]],
        "acc": [54, 49, 338, 346, 321, 70, 64, 75],
    },
    "obstacle-class": {
        "numer": [[0, 0, 0, 0], [0, 15, 0, 14], [2, 0, 4, 16], [2, 1, 1, 5],
                  [1, 1, 0, 9], [0, 0, 0, 3], [0, 0, 1, 0], [2, 1, 0, 9]],
        "acc": [11, 15, 214, 28, 202, 186, 24, 213],
    },
}


@pytest.fixture
def pre_noise_stream():
    """The goldens were drawn from jax's original Threefry stream (evidence and
    the unfused node keys come from ``jax.random``); pin it, whatever the
    installed jax defaults to."""
    with jax.threefry_partitionable(False):
        yield


def _gold_ev(spec):
    return sample_evidence(spec, jax.random.PRNGKey(3), 8)


def _bits(post):
    return np.asarray(post, np.float32).view(np.uint32)


def _slot_counts(post, acc, q_cards):
    """Per-slot numerator counts behind a count-exact ``run`` posterior."""
    post = np.asarray(post, np.float64)
    acc = np.asarray(acc, np.float64)[:, None]
    if post.ndim == 2:
        counts = post * acc
    else:
        counts = np.concatenate(
            [post[:, q, 1:c] * acc for q, c in enumerate(q_cards)], axis=1
        )
    # each slot is count / accepted, correctly rounded: the count comes back
    np.testing.assert_allclose(counts, np.rint(counts), atol=1e-3)
    return np.rint(counts).astype(np.int64)


@pytest.mark.parametrize("name", sorted(_GOLD_FUSED))
def test_no_noise_fused_bit_identical_to_pre_noise_tree(name, pre_noise_stream):
    spec = by_name(name)
    gold = _GOLD_FUSED[name]
    for noise in (None, NoiseModel.zero(), NoiseModel().scaled(0.0)):
        net = compile_network(spec, n_bits=1024, noise=noise)
        post, dec, acc = net.decide(jax.random.PRNGKey(0), _gold_ev(spec))
        np.testing.assert_array_equal(_bits(post), np.asarray(gold["post_bits"], np.uint32))
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(gold["dec"]))
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(gold["acc"]))


@pytest.mark.parametrize("name", sorted(_GOLD_UNFUSED))
def test_no_noise_unfused_bit_identical_to_pre_noise_tree(name, pre_noise_stream):
    spec = by_name(name)
    gold = _GOLD_UNFUSED[name]
    net = compile_network(spec, n_bits=1024, fused=False)
    post, acc = net.run(jax.random.PRNGKey(0), _gold_ev(spec))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(gold["acc"]))
    np.testing.assert_array_equal(
        _slot_counts(post, acc, net.query_cards), np.asarray(gold["numer"])
    )


# --- perturbation mechanics --------------------------------------------------------

def test_model_validation():
    with pytest.raises(ValueError):
        NoiseModel(d2d_cv=-0.1)
    with pytest.raises(ValueError):
        NoiseModel(read_cv=float("nan"))
    with pytest.raises(ValueError):
        NoiseModel(ir_drop=1.0)
    with pytest.raises(ValueError):
        NoiseModel(p_stuck_on=0.7, p_stuck_off=0.7)
    with pytest.raises(TypeError):
        compile_network(by_name("sensor-degradation"), n_bits=32, noise=0.1)


def test_zero_and_scaled_models():
    assert NoiseModel.zero().is_zero
    assert NoiseModel().scaled(0.0).is_zero
    assert not NoiseModel().is_zero
    half = NoiseModel().scaled(0.5)
    assert half.d2d_cv == pytest.approx(NoiseModel().d2d_cv * 0.5)
    assert half.seed == NoiseModel().seed
    cy = NoiseModel().with_cycle(7)
    assert cy.cycle == 7 and cy.seed == NoiseModel().seed
    assert cy.d2d_cv == NoiseModel().d2d_cv


def test_default_magnitudes_tied_to_device_model():
    """The nominal NoiseModel IS the paper-calibrated device model: the d2d
    spread is Fig 1d's 8 % CV verbatim, and the read CV is the stationary
    V_th CV attenuated by the ~80 switching cycles one bit integrates."""
    m = NoiseModel()
    assert m.d2d_cv == DEFAULT_PARAMS.d2d_cv == 0.08
    assert m.read_cv == DEFAULT_PARAMS.read_cv
    assert DEFAULT_PARAMS.reads_per_bit == pytest.approx(80.0)
    assert DEFAULT_PARAMS.read_cv == pytest.approx(
        (DEFAULT_PARAMS.vth_sigma / DEFAULT_PARAMS.vth_mu) / np.sqrt(80.0)
    )
    assert NoiseModel.nominal(DEFAULT_PARAMS) == m


@pytest.mark.parametrize("name", ["intersection", "obstacle-class"])
def test_perturbed_rows_valid_and_deterministic(name):
    spec = by_name(name)
    m = NoiseModel()
    rows = perturbed_cdf_rows(spec, m)
    again = perturbed_cdf_rows(spec, m)
    assert rows == again                       # pure function of the model
    assert set(rows) == {n.name for n in spec.nodes}
    changed = 0
    for node in spec.nodes:
        clean = tuple(rng.cdf_thresholds_int(r) for r in spec.cpt_rows(node.name))
        for prow, crow in zip(rows[node.name], clean):
            assert len(prow) == len(crow) == spec.card(node.name) - 1
            assert all(0 <= t <= 256 for t in prow)
            # cumulative tails stay non-increasing (valid CDF rows)
            assert all(a >= b for a, b in zip(prow, prow[1:]))
            changed += int(prow != crow)
    assert changed > 0                          # nominal noise is material
    # a different array instance draws different devices
    assert perturbed_cdf_rows(spec, dataclasses.replace(m, seed=1)) != rows


def test_cycle_redraws_only_read_noise():
    spec = by_name("pedestrian-night")
    full = NoiseModel()
    assert perturbed_cdf_rows(spec, full) != perturbed_cdf_rows(spec, full.with_cycle(3))
    d2d_only = NoiseModel(read_cv=0.0, ir_drop=0.0, p_stuck_on=0.0, p_stuck_off=0.0)
    assert perturbed_cdf_rows(spec, d2d_only) == perturbed_cdf_rows(
        spec, d2d_only.with_cycle(3)
    )


def test_scaled_zero_returns_clean_thresholds():
    spec = by_name("lane-change")
    rows = perturbed_cdf_rows(spec, NoiseModel().scaled(0.0))
    for node in spec.nodes:
        clean = tuple(rng.cdf_thresholds_int(r) for r in spec.cpt_rows(node.name))
        assert rows[node.name] == clean


def test_stuck_at_extremes():
    spec = by_name("pedestrian-night")
    quiet = dict(d2d_cv=0.0, read_cv=0.0, ir_drop=0.0)
    all_on = perturbed_cdf_rows(spec, NoiseModel(p_stuck_on=1.0, p_stuck_off=0.0, **quiet))
    all_off = perturbed_cdf_rows(spec, NoiseModel(p_stuck_on=0.0, p_stuck_off=1.0, **quiet))
    for name in all_on:
        assert all(t == 256 for row in all_on[name] for t in row)
        assert all(t == 0 for row in all_off[name] for t in row)


# --- noisy agreement: compiled programs vs the perturbed-CPT oracle twin -----------

N_BITS = 1 << 14


def _assert_3sigma(post, exact, acc, tail=0.01, hard=6.0):
    post, exact, acc = np.asarray(post), np.asarray(exact), np.asarray(acc)
    keep = acc > 50
    assert keep.mean() > 0.5, f"acceptance collapsed: {keep.mean()}"
    extra = (np.ndim(exact) - 1) * (None,)
    sigma = np.sqrt(np.clip(exact * (1 - exact), 1e-3, None) / acc[(slice(None),) + extra])
    z = (np.clip(np.abs(post - exact) - 2 / 256, 0, None) / sigma)[keep]
    assert np.mean(z > 3.0) < tail, float(np.max(z))
    assert float(np.max(z)) < hard


@pytest.mark.parametrize("name", ["pedestrian-night", "intersection", "obstacle-class"])
def test_fused_matches_perturbed_oracle_3sigma(name):
    spec = by_name(name)
    m = NoiseModel()
    net = compile_network(spec, n_bits=N_BITS, noise=m)
    assert net.fused and net.noise == m
    ev = sample_evidence(spec, jax.random.PRNGKey(2), 256)
    post, acc = net.run(jax.random.PRNGKey(0), ev)
    exact, _ = make_posterior_fn(spec, noise=m)(ev)
    _assert_3sigma(post, exact, acc)


def test_unfused_matches_perturbed_oracle_3sigma():
    spec = by_name("pedestrian-night")
    m = NoiseModel()
    net = compile_network(spec, n_bits=N_BITS, fused=False, noise=m)
    ev = sample_evidence(spec, jax.random.PRNGKey(2), 64)
    post, acc = net.run(jax.random.PRNGKey(0), ev)
    exact, _ = make_posterior_fn(spec, noise=m)(ev)
    _assert_3sigma(post, exact, acc)


def test_noise_shifts_the_oracle():
    """The nominal model moves posteriors by much more than the DAC grid --
    agreement with the PERTURBED oracle is a real constraint, not slack."""
    spec = by_name("pedestrian-night")
    ev = sample_evidence(spec, jax.random.PRNGKey(2), 256)
    clean, _ = make_posterior_fn(spec, dac_quantize=True)(ev)
    noisy, _ = make_posterior_fn(spec, noise=NoiseModel())(ev)
    assert float(np.max(np.abs(np.asarray(clean) - np.asarray(noisy)))) > 0.02
