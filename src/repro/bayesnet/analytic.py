"""Exact enumeration oracle for :class:`~repro.bayesnet.spec.NetworkSpec`.

Full-joint enumeration over the ``prod(k_i)`` mixed-radix assignments,
vectorised: the assignment grid, the per-node CPT gathers and the
evidence-consistency masks are all plain array ops, so one jit launch
evaluates *batches* of evidence frames against the whole joint at once.  For
the scenario networks this is exact, fast, and serves as the correctness
bound for the stochastic backend (compiled posteriors must match within
O(1/sqrt(n_accepted))).

``dac_quantize=True`` snaps every CPT row to the distribution the 8-bit DAC
CDF actually samples: the cumulative tail thresholds are rounded to the
``t/256`` grid (``rng.cdf_thresholds_int``) and differenced back into
per-value probabilities -- so oracle-vs-stochastic comparisons isolate the
stochastic noise from the (documented, bounded) quantisation bias.  For a
binary node this reduces to the classic ``round(p * 256) / 256``.

``noise=`` (a :class:`~repro.bayesnet.noise.NoiseModel`) makes this the
**perturbed-CPT oracle twin** of ``compile_network(noise=...)``: the same
deterministic threshold perturbation the compiler bakes into its plan is
applied here, and the enumeration runs over the *perturbed* integer
thresholds differenced back to probabilities.  The compiled program then
samples exactly the network this oracle enumerates, so 3-sigma agreement
tests keep an exact ground truth under any noise level.  The perturbation
acts on the integer DAC grid, so ``noise`` subsumes ``dac_quantize``: the
perturbed thresholds ARE the quantisation, and the flag is ignored when a
model is given.

:func:`filter_posteriors` is the plain reference of a *tracked* spec: one
exact enumeration step per frame under the prior the tracker predicts for it.

Posterior layout mirrors the compiler: all-binary query sets keep the classic
``(B, n_q)`` array of ``P(q=1)``; any k-ary query switches to ``(B, n_q,
max_k)`` normalised per-value posteriors (zero-padded past each query's
cardinality, uniform over the query's values where the evidence is
impossible).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.bayesnet.noise import NoiseModel, perturbed_cdf_rows
from repro.bayesnet.spec import NetworkSpec
from repro.core import rng
from repro.kernels.net_sweep.common import epoch_word_bounds

_MAX_STATES = 1 << 20


def _node_rows(
    spec: NetworkSpec,
    name: str,
    dac_quantize: bool,
    perturbed=None,
) -> np.ndarray:
    """(L, k) float32 canonical (optionally DAC-snapped / perturbed) CPT rows.

    ``perturbed`` (a name -> integer CDF rows dict from
    :func:`~repro.bayesnet.noise.perturbed_cdf_rows`) takes precedence: the
    perturbed thresholds are differenced back to per-value probabilities, the
    exact distribution the noisy compiled program samples.
    """
    rows = spec.cpt_rows(name)
    if perturbed is not None:
        snapped = []
        for prow in perturbed[name]:
            bounds = (256,) + tuple(prow) + (0,)
            snapped.append(
                tuple((bounds[v] - bounds[v + 1]) / 256.0 for v in range(len(prow) + 1))
            )
        rows = tuple(snapped)
    elif dac_quantize:
        snapped = []
        for row in rows:
            bounds = (256,) + rng.cdf_thresholds_int(row) + (0,)
            snapped.append(
                tuple((bounds[v] - bounds[v + 1]) / 256.0 for v in range(len(row)))
            )
        rows = tuple(snapped)
    return np.asarray(rows, np.float32)


def joint_table(
    spec: NetworkSpec,
    dac_quantize: bool = False,
    noise: NoiseModel | None = None,
    *,
    drift_epochs: int = 1,
    program: dict | None = None,
    n_bits: int | None = None,
):
    """Returns (states (S, N) int32, joint (S,) float32), S = prod(cards).

    Column ``j`` of ``states`` is the value of ``spec.nodes[j]`` (node 0 is
    the fastest-cycling mixed-radix digit, the k-ary generalisation of the
    old bit grid); ``joint`` is the exact probability of each assignment.
    ``noise`` enumerates the *perturbed* network (see module docstring).

    ``drift_epochs=E > 1`` is the oracle twin of the epoched sweep: the joint
    is the *mixture* ``sum_e w_e * joint_e`` of the per-epoch perturbed
    joints (epoch ``e`` at ``noise.with_cycle(noise.cycle + e)``), with
    ``w_e`` each epoch's exact share of the packed words when ``n_bits`` is
    given (:func:`~repro.kernels.net_sweep.common.epoch_word_bounds`) and
    uniform otherwise.  The sweep's count-ratio estimator sums counts across
    all epochs of the stream, so its large-``n_bits`` limit is exactly the
    posterior of this mixed joint.  ``program`` matches the compiler's
    programmed-threshold override.
    """
    drift_epochs = int(drift_epochs)
    if drift_epochs > 1 and noise is None:
        raise ValueError("drift_epochs > 1 needs a NoiseModel to advance")
    cards = spec.cards()
    total = math.prod(cards)
    if total > _MAX_STATES:
        raise ValueError(
            f"enumeration oracle capped at {_MAX_STATES} joint states, got {total}"
        )
    idx = {node.name: j for j, node in enumerate(spec.nodes)}
    s = np.arange(total, dtype=np.int64)
    cols = []
    for c in cards:
        cols.append((s % c).astype(np.int32))
        s //= c
    states = jnp.asarray(np.stack(cols, axis=-1))

    def one_epoch_joint(perturbed):
        joint = jnp.ones((total,), jnp.float32)
        for node in spec.nodes:
            cpt = jnp.asarray(_node_rows(spec, node.name, dac_quantize, perturbed))
            # Mixed-radix CPT row index: first parent is the most significant
            # digit (spec.py convention).
            row = jnp.zeros((total,), jnp.int32)
            for parent in node.parents:
                row = row * jnp.int32(spec.card(parent)) + states[:, idx[parent]]
            joint = joint * cpt[row, states[:, idx[node.name]]]
        return joint

    if noise is None and program is None:
        return states, one_epoch_joint(None)
    if drift_epochs == 1:
        return states, one_epoch_joint(
            perturbed_cdf_rows(spec, noise, program=program)
        )
    if n_bits is not None:
        bounds = epoch_word_bounds(n_bits // 32, drift_epochs)
        spans = np.diff(bounds).astype(np.float64)
        weights = spans / max(spans.sum(), 1.0)
    else:
        weights = np.full((drift_epochs,), 1.0 / drift_epochs)
    joint = jnp.zeros((total,), jnp.float32)
    for e, w_e in enumerate(weights):
        pe = perturbed_cdf_rows(
            spec, noise.with_cycle(noise.cycle + e), program=program
        )
        joint = joint + jnp.float32(w_e) * one_epoch_joint(pe)
    return states, joint


def make_posterior_fn(
    spec: NetworkSpec,
    queries: Sequence[str] | None = None,
    evidence: Sequence[str] | None = None,
    dac_quantize: bool = False,
    noise: NoiseModel | None = None,
    *,
    drift_epochs: int = 1,
    program: dict | None = None,
    n_bits: int | None = None,
) -> Callable[[jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]:
    """Compile the exact batched-posterior function for a spec.

    Returns ``fn(ev_frames (B, n_ev) int) -> (post, p_evidence (B,))`` with
    the posterior layout described in the module docstring, jitted and fully
    vectorised over frames.  Frames columns follow the ``evidence`` order and
    hold one value in ``[0, card)`` per node; ``p_evidence`` is the evidence
    marginal (0 where impossible; the posterior then falls back to 0.5 /
    uniform).  ``noise`` builds the perturbed-CPT oracle twin of
    ``compile_network(noise=...)`` -- exact ground truth for the noisy
    program (see module docstring).  ``drift_epochs`` / ``program`` /
    ``n_bits`` mirror the compiler's epoched calibrate-back lowering: the
    oracle becomes the exact word-weighted epoch mixture the swept stream's
    count-ratio estimator converges to (see :func:`joint_table`).
    """
    queries = tuple(queries if queries is not None else spec.queries)
    evidence = tuple(evidence if evidence is not None else spec.evidence)
    states, joint = joint_table(
        spec, dac_quantize=dac_quantize, noise=noise,
        drift_epochs=drift_epochs, program=program, n_bits=n_bits,
    )
    ev_cols = jnp.asarray([spec.index(e) for e in evidence], jnp.int32)
    q_cols = jnp.asarray([spec.index(q) for q in queries], jnp.int32)
    q_cards = tuple(spec.card(q) for q in queries)
    all_binary = all(c == 2 for c in q_cards)
    kmax = max(q_cards) if q_cards else 2

    @jax.jit
    def posterior(ev_frames: jnp.ndarray):
        ev = jnp.asarray(ev_frames, jnp.int32)
        assert ev.ndim == 2 and ev.shape[1] == len(evidence), ev.shape
        # (B, S): does assignment s agree with frame b's evidence?
        if len(evidence):
            match = jnp.all(states[None, :, ev_cols] == ev[:, None, :], axis=-1)
        else:
            match = jnp.ones((ev.shape[0], states.shape[0]), bool)
        w = match.astype(jnp.float32) * joint[None, :]            # (B, S)
        p_e = jnp.sum(w, axis=-1)                                 # (B,)
        if all_binary:
            q_on = states[:, q_cols].astype(jnp.float32)          # (S, n_q)
            num = w @ q_on                                        # (B, n_q)
            post = jnp.where(
                p_e[:, None] > 0, num / jnp.maximum(p_e[:, None], 1e-30), 0.5
            )
            return post, p_e
        posts = []
        for qi, c in enumerate(q_cards):
            onehot = (
                states[:, q_cols[qi], None] == jnp.arange(kmax, dtype=jnp.int32)
            ).astype(jnp.float32)                                 # (S, kmax)
            num = w @ onehot                                      # (B, kmax)
            fallback = jnp.asarray(
                [1.0 / c if v < c else 0.0 for v in range(kmax)], jnp.float32
            )
            posts.append(
                jnp.where(
                    p_e[:, None] > 0,
                    num / jnp.maximum(p_e[:, None], 1e-30),
                    fallback[None, :],
                )
            )
        return jnp.stack(posts, axis=1), p_e                      # (B, n_q, kmax)

    return posterior


@functools.partial(jax.jit, static_argnames=("spec", "batch"))
def _sample_joint(spec: NetworkSpec, key: jax.Array, batch: int) -> jnp.ndarray:
    """Ancestral sampling: (B, N) int32 values in declared node order."""
    idx = {node.name: j for j, node in enumerate(spec.nodes)}
    vals = [None] * spec.n_nodes
    for name in spec.topo_order():
        node = spec.node(name)
        key, sub = jax.random.split(key)
        # (L, k-1) cumulative tails: value = #{v : u < P(value >= v)} -- the
        # float twin of the DAC CDF sampler (binary: one column equal to p1).
        rows = np.asarray(spec.cpt_rows(name), np.float32)
        tails = jnp.asarray(
            np.cumsum(rows[:, ::-1], axis=-1)[:, ::-1][:, 1:], jnp.float32
        )
        row = jnp.zeros((batch,), jnp.int32)
        for parent in node.parents:
            row = row * jnp.int32(spec.card(parent)) + vals[idx[parent]]
        u = jax.random.uniform(sub, (batch,))
        vals[idx[name]] = jnp.sum(
            (u[:, None] < tails[row]).astype(jnp.int32), axis=-1
        )
    return jnp.stack(vals, axis=-1)


def sample_evidence(
    spec: NetworkSpec, key: jax.Array, batch: int,
    evidence: Sequence[str] | None = None,
) -> jnp.ndarray:
    """Draw (B, n_ev) realistic evidence frames by ancestral joint sampling.

    Frames are distributed as the network itself predicts its sensors to fire,
    so batched benchmarks exercise the acceptance rates a deployment would see.
    """
    evidence = tuple(evidence if evidence is not None else spec.evidence)
    full = _sample_joint(spec, key, batch)
    cols = jnp.asarray([spec.index(e) for e in evidence], jnp.int32)
    return full[:, cols]


def filter_posteriors(
    spec: NetworkSpec,
    evidence,
    track_ids,
    fresh,
    answered_posts=None,
    answered_accepted=None,
):
    """Exact filtering reference of a tracked spec: one step per frame.

    ``evidence`` (F, n_ev) int rows in ``spec.evidence`` order, ``track_ids``
    (F,) and ``fresh`` (F,) as the tracked program takes them, frames in the
    order they are decided.  Returns ``(post, p_e, prior)``: the posterior
    of each frame in :func:`make_posterior_fn`'s layout, its exact evidence
    probability, and the (F, k) DAC-quantised prior it was decided under.

    Per frame, in float64 numpy over the full joint: the predicted prior is
    the published prior for a fresh track; otherwise the track's previous
    belief times the transition.  The previous belief is the track's
    previous answered posterior over the state when ``answered_posts`` (the
    program's outputs, same layout) and ``answered_accepted`` (their
    accepted counts) are given, else the exact previous posterior.  Every
    other CPT row is DAC-quantised as in :func:`make_posterior_fn`
    (``dac_quantize=True``).

    Two departures from a textbook Bayes filter, both the tracked program's:

    * the predicted prior is quantised with the same 8-bit DAC as every CPT
      row (``rng.cdf_thresholds_int``) before the frame is decided, so each
      step conditions on a prior on the ``t / 256`` grid, not the exact one;
    * a frame that accepted no sample (an all-zero answered posterior, or
      evidence of probability 0) keeps the unquantised prediction as its
      track's belief: an empty sample leaves only the transition's move.
    """
    if spec.state is None:
        raise ValueError(f"{spec.name}: filter_posteriors needs a tracked spec")
    if (answered_posts is None) != (answered_accepted is None):
        raise ValueError("answered_posts and answered_accepted come together")
    root, trans = spec.state
    k = spec.card(root)
    trans = np.asarray(trans, np.float64)
    published = np.asarray(spec.cpt_rows(root)[0], np.float64)
    queries = spec.queries
    q_cards = tuple(spec.card(q) for q in queries)
    all_binary = all(c == 2 for c in q_cards)
    kmax = max(q_cards)
    cards = spec.cards()
    total = math.prod(cards)
    if total > _MAX_STATES:
        raise ValueError(
            f"enumeration oracle capped at {_MAX_STATES} joint states, got {total}"
        )
    s = np.arange(total, dtype=np.int64)
    states = []
    for c in cards:
        states.append(s % c)
        s //= c
    states = np.stack(states, axis=-1)
    idx = {node.name: j for j, node in enumerate(spec.nodes)}
    rest = np.ones(total, np.float64)          # every factor but the state root's
    for node in spec.nodes:
        if node.name == root:
            continue
        cpt = _node_rows(spec, node.name, True).astype(np.float64)
        row = np.zeros(total, np.int64)
        for parent in node.parents:
            row = row * spec.card(parent) + states[:, idx[parent]]
        rest *= cpt[row, states[:, idx[node.name]]]
    root_vals = states[:, idx[root]]
    ev_states = states[:, [idx[e] for e in spec.evidence]]
    q_states = states[:, [idx[q] for q in queries]]
    state_q = queries.index(root)

    evidence = np.asarray(evidence, np.int64).reshape(len(track_ids), -1)
    n = len(evidence)
    post = np.zeros((n, len(queries)) if all_binary else (n, len(queries), kmax))
    p_e = np.zeros(n)
    priors = np.zeros((n, k))
    belief = {}
    for f in range(n):
        t = int(track_ids[f])
        if fresh[f]:
            pred = published
        elif t in belief:
            pred = belief[t] @ trans
        else:
            raise ValueError(f"frame {f}: track {t} continues but never started")
        bounds = (256,) + rng.cdf_thresholds_int(tuple(pred)) + (0,)
        prior = np.asarray([(bounds[v] - bounds[v + 1]) / 256.0 for v in range(k)])
        priors[f] = prior
        w = rest * prior[root_vals] * np.all(ev_states == evidence[f], axis=-1)
        p_e[f] = w.sum()
        for qi, c in enumerate(q_cards):
            mass = np.bincount(q_states[:, qi], weights=w, minlength=c)
            p = mass / p_e[f] if p_e[f] > 0 else np.full(c, 1.0 / c)
            if all_binary:
                post[f, qi] = p[1]
            else:
                post[f, qi, :c] = p
        if answered_posts is None:
            b = (post[f, state_q, :k] if not all_binary
                 else np.asarray([1 - post[f, state_q], post[f, state_q]]))
            belief[t] = b if p_e[f] > 0 else pred
        elif answered_accepted[f] > 0:
            a = np.asarray(answered_posts[f], np.float64)
            belief[t] = a[state_q, :k] if a.ndim == 2 else np.asarray(
                [1 - a[state_q], a[state_q]])
        else:
            belief[t] = pred
    return post, p_e, priors

