"""Shared kernel helpers: the tiling-safe block ladder and the Mosaic-safe
argmax epilogue, run inside Pallas kernels in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.bayesnet import by_name, sweep_plan
from repro.kernels import backend
from repro.kernels.net_sweep.common import decide_counts


@pytest.mark.parametrize("preferred", [128, 256, 512])
def test_pick_block_keeps_the_tiling_rule(preferred):
    """A block row count is a multiple of 8 or spans every row, and tiles them."""
    for rows in range(1, 2049):
        block = backend.pick_block(rows, preferred)
        assert rows % block == 0, (rows, block)
        assert block % 8 == 0 or block == rows, (rows, block)


def test_pick_block_short_driver_buckets_span_the_batch():
    assert [backend.pick_block(b, 128) for b in (1, 2, 4, 8, 32)] == [1, 2, 4, 8, 32]


def _in_kernel(fn, out_cols, *arrays):
    """Apply ``fn`` to whole-array blocks inside an interpreted Pallas kernel."""

    def kernel(*refs):
        *ins, out = refs
        out[...] = fn(*(r[...] for r in ins))

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((arrays[0].shape[0], out_cols), jnp.int32),
        interpret=True,
    )(*arrays)


def _count_rows(k, rng):
    rows = [
        np.zeros(k),                      # no stream position accepted
        np.full(k, 7),                    # every value tied
        np.r_[9, 9, np.zeros(k - 2)],     # a tie at the top
        np.r_[np.zeros(k - 1), 5],        # only the last value counted
    ]
    rows += list(rng.integers(0, 3, size=(12, k)))  # small range: ties abound
    return jnp.asarray(np.stack(rows), jnp.int32)


@pytest.mark.parametrize("k", [2, 3, 5, 128])
def test_first_argmax_in_kernel_equals_argmax(k):
    counts = _count_rows(k, np.random.default_rng(k))
    got = _in_kernel(lambda c: backend.first_argmax(c)[:, None], 1, counts)
    np.testing.assert_array_equal(
        np.asarray(got[:, 0]), np.asarray(jnp.argmax(counts, axis=-1))
    )


@pytest.mark.parametrize("name", ["obstacle-class", "intersection-cat", "pedestrian-night"])
def test_decide_epilogue_in_kernel_equals_argmax(name):
    """``decide_counts`` picks what ``jnp.argmax`` picks on the full count
    vector, ties to the lowest value and ``denom == 0`` to value 0."""
    spec = by_name(name)
    plan = sweep_plan(spec, spec.queries, spec.evidence)
    rng = np.random.default_rng(len(name))
    b = 64
    numer = rng.integers(0, 3, size=(b, plan.n_value_slots))
    sums = [numer[:, off:off + c - 1].sum(-1)
            for c, off in zip(plan.query_cards, plan.slot_offsets)]
    denom = np.max(sums, axis=0) + rng.integers(0, 3, size=b)
    numer[:4] = 0
    denom[:4] = 0
    numer, denom = jnp.asarray(numer, jnp.int32), jnp.asarray(denom, jnp.int32)

    got = _in_kernel(
        lambda n, d: decide_counts(plan, n, d[:, 0]), len(plan.query_cards),
        numer, denom[:, None],
    )
    want = []
    for c, off in zip(plan.query_cards, plan.slot_offsets):
        slots = numer[:, off:off + c - 1]
        full = jnp.concatenate([(denom - slots.sum(-1))[:, None], slots], axis=-1)
        want.append(jnp.argmax(full, axis=-1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(jnp.stack(want, -1)))
    assert np.all(np.asarray(got[:4]) == 0)
