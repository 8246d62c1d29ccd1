"""jit'd public wrappers for the node-MUX sweep (the bayesnet compiler's inner op).

``node_mux`` turns one binary Bayesian-network node into its packed stochastic
stream; ``node_mux_categorical`` generalises the gather mode to cardinality-k
nodes (value bit-planes sampled from one byte against the parent-gathered DAC
CDF).  The binary modes, identical in distribution:

* ``mode='gather'`` (default, production): gather the node's 8-bit DAC
  threshold by the parents' packed bits, then compare one entropy byte per
  stream bit -- ``2**m`` times less entropy than row-encode and no stream-wide
  MUX tree (the select collapses to a threshold gather).
* ``mode='rows'`` (the original formulation, kept as the statistical
  verification baseline): encode all ``2**m`` CPT rows with fresh entropy and
  MUX-select by the parents' packed streams (the n-ary Fig S8 tree).

Dispatch follows the other kernel ops: Pallas kernel where it compiles,
bit-exact jnp reference as the CPU production fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitops, rng
from repro.kernels import backend
from repro.kernels.node_mux.kernel import (
    node_mux_cat_pallas,
    node_mux_gather_pallas,
    node_mux_pallas,
)
from repro.kernels.node_mux.ref import (
    node_mux_cat_ref,
    node_mux_gather_ref,
    node_mux_ref,
)


@functools.partial(jax.jit, static_argnames=("n_bits", "mode", "use_kernel", "interpret"))
def node_mux(
    key: jax.Array,
    cpt: jnp.ndarray,
    parents: jnp.ndarray,
    n_bits: int = 128,
    *,
    mode: str = "gather",
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Lower one network node to its packed stream.

    cpt:     (..., L) CPT rows P(node=1 | parent assignment), L = 2**m, row
             index with the FIRST parent as the most significant bit.
    parents: (m, ..., n_words) packed parent streams (leading dims match cpt).
    Returns (..., n_words) uint32.  n_bits must be a multiple of 32.

    ``mode='gather'`` draws ONE counter-entropy byte per stream bit and
    compares it against the parent-gathered threshold; ``mode='rows'`` draws
    fresh entropy per CPT row (one SNE per row) and MUX-selects.  Conditional
    on the parents' bits the output bit is Bernoulli(cpt[row]) either way and
    positions stay conditionally independent, so the two modes sample the
    same joint -- asserted statistically in tests.  The two modes consume
    differently-shaped entropy, so their streams are not bit-identical.
    """
    assert n_bits % 32 == 0, "kernel path consumes whole uint32 entropy words"
    if mode not in ("gather", "rows"):
        raise ValueError(f"unknown node_mux mode {mode!r}")
    interpret = backend.resolve_interpret(interpret)
    # the TPU compiler (Mosaic) refuses this kernel (float32 -> uint32 casts,
    # uint32 reductions): the bit-exact reference is the default everywhere
    use_kernel = bool(use_kernel)
    cpt = jnp.asarray(cpt, jnp.float32)
    m = parents.shape[0]
    l = cpt.shape[-1]
    assert l == 1 << m, f"{l} CPT rows for {m} parents"
    lead = cpt.shape[:-1]
    w = n_bits // 32
    assert parents.shape == (m,) + lead + (w,), (parents.shape, lead)
    flat_cpt = cpt.reshape(-1, l)
    flat_par = parents.reshape(m, -1, w)
    rows = flat_cpt.shape[0]
    block = backend.pick_block(rows, 256)
    if mode == "gather":
        rand = rng.counter_hash_words(key, (rows,), n_bits // 4)
        if use_kernel:
            out = node_mux_gather_pallas(
                flat_cpt, rand, flat_par, block_r=block, interpret=interpret
            )
        else:
            out = node_mux_gather_ref(flat_cpt, rand, flat_par)
    else:
        rand = rng.counter_hash_words(key, (rows, l), n_bits // 4)
        if use_kernel:
            out = node_mux_pallas(flat_cpt, rand, flat_par, block_r=block, interpret=interpret)
        else:
            out = node_mux_ref(flat_cpt, rand, flat_par)
    return out.reshape(lead + (w,))


@functools.partial(
    jax.jit, static_argnames=("cards", "n_bits", "use_kernel", "interpret")
)
def node_mux_categorical(
    key: jax.Array,
    cdf: jnp.ndarray,
    parents: jnp.ndarray,
    *,
    cards: tuple,
    n_bits: int = 128,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Lower one cardinality-``k`` network node to its packed value bit-planes.

    cdf:     (..., L, k-1) uint32 non-increasing cumulative DAC thresholds per
             mixed-radix CPT row (``rng.cdf_thresholds_int``; L = product of
             parent cardinalities, first parent = most significant digit).
    parents: (P, ..., n_words) packed parent value bit-planes; parent ``j``
             owns the contiguous block of ``value_bits(k_j)`` planes, LSB
             first (leading dims match cdf's).
    cards:   static ``(k, k_p0, .., k_pm-1)`` -- node then parent cardinalities.
    Returns ``(value_bits(k),) + lead + (n_words,)`` uint32.

    The categorical generalisation of ``mode='gather'``: ONE counter-entropy
    byte per stream position samples the whole k-way draw against the
    parent-gathered CDF.  n_bits must be a multiple of 32.
    """
    assert n_bits % 32 == 0, "kernel path consumes whole uint32 entropy words"
    interpret = backend.resolve_interpret(interpret)
    # the TPU compiler (Mosaic) refuses this kernel (float32 -> uint32 casts,
    # uint32 reductions): the bit-exact reference is the default everywhere
    use_kernel = bool(use_kernel)
    k = int(cards[0])
    pcards = tuple(int(c) for c in cards[1:])
    l = 1
    p = 0
    for c in pcards:
        l *= c
        p += bitops.value_bits(c)
    cdf = jnp.asarray(cdf, jnp.uint32)
    assert cdf.shape[-2:] == (l, k - 1), (cdf.shape, (l, k - 1))
    lead = cdf.shape[:-2]
    w = n_bits // 32
    assert parents.shape == (p,) + lead + (w,), (parents.shape, lead)
    flat_cdf = cdf.reshape((-1, l, k - 1))
    flat_par = parents.reshape(p, -1, w)
    rows = flat_cdf.shape[0]
    block = backend.pick_block(rows, 256)
    rand = rng.counter_hash_words(key, (rows,), n_bits // 4)
    if use_kernel:
        out = node_mux_cat_pallas(
            flat_cdf, rand, flat_par, cards=cards, block_r=block, interpret=interpret
        )
    else:
        out = node_mux_cat_ref(flat_cdf, rand, flat_par, cards)
    return out.reshape((out.shape[0],) + lead + (w,))
