"""Counter-based entropy for packed stochastic encoding (DESIGN.md SS2/SS3).

The hot-path encoders used to draw a full float32 ``(..., n_bits)`` uniform
tensor -- 32 bits of entropy traffic per emitted stream bit -- and then pay a
shift-reduce ``pack_bits`` to get into the packed domain.  This module is the
packed-domain replacement: entropy comes as counter-based uint32 words (the
TPU stand-in for the memristor's stochastic V_th), each word contributes its
4 bytes as 4 independent uniform(0..255) draws, and a stream bit is 1 iff
``byte < round(p * 256)``.  That is exactly the scheme the
``kernels/sne_encode`` Pallas kernel uses, so the core encoders and the
kernel stay bit-compatible.

Two generators produce the words: the default ``counter_hash_words`` (keyed
counters through two lowbias32 avalanche rounds -- the entropy-bound hot
path's fast generator) and ``jax.random.bits`` Threefry
(``random_words(..., impl='threefry')``) when reproducibility against other
JAX code matters more than speed.

Per stream bit this costs 8 bits of entropy (4x less traffic than the float
path) and the output is *born packed* -- no per-bit intermediates, no
``pack_bits`` -- which is where the ~32x hot-loop win comes from.

Probabilities are quantised to 8 bits (the V_in programming DAC of the
hardware SNE): max quantisation error 1/512, far below the O(1/sqrt(n_bits))
stochastic noise floor for every stream length used in practice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops

# Stream bits contributed by one uint32 entropy word (one per byte).
BITS_PER_RAND_WORD = 4
# Entropy words consumed per packed output word (32 stream bits / 4 per word).
RAND_WORDS_PER_OUT_WORD = 8


def threshold_from_p(p: jnp.ndarray) -> jnp.ndarray:
    """Probability -> 8-bit comparator threshold in [0, 256] (uint32)."""
    p = jnp.asarray(p, jnp.float32)
    # through int32: Mosaic has no float32 -> uint32 cast, and [0, 256] fits
    t = jnp.clip(jnp.round(p * 256.0), 0.0, 256.0).astype(jnp.int32)
    return t.astype(jnp.uint32)


def threshold_int(p: float) -> int:
    """:func:`threshold_from_p` for one Python float, evaluated at trace time.

    Static lowerings (the fused sweep's :class:`SweepPlan`) bake thresholds in
    as ints; this is the same grid -- float32 ``p * 256`` is exact in numpy
    and XLA alike, so half-even rounding agrees bit-for-bit.
    """
    return int(np.clip(np.round(np.float32(p) * 256.0), 0.0, 256.0))


def cdf_thresholds_int(probs) -> tuple:
    """Per-value probabilities ``(p_0, .., p_{k-1})`` -> ``(k-1,)`` cumulative
    8-bit DAC thresholds, evaluated at trace time (Python floats in, ints out).

    Threshold ``C_v`` encodes ``P(value >= v)``: one entropy byte samples the
    whole categorical draw as ``value = #{v : byte < C_v}``.  Tail sums are
    non-increasing, so the rounded thresholds are too (enforced defensively) --
    the nesting the bit-sliced comparator chain relies on.  For k=2 the single
    threshold is exactly :func:`threshold_int` of ``P(value=1)``, which keeps
    binary nodes bit-identical to the scalar-threshold lowering.
    """
    k = len(probs)
    if k < 2:
        raise ValueError(f"need >= 2 value probabilities, got {k}")
    out = []
    prev = 256
    for v in range(1, k):
        tail = float(np.sum(np.asarray(probs[v:], np.float64)))
        t = min(threshold_int(tail), prev)
        out.append(t)
        prev = t
    return tuple(out)


def cdf_thresholds(probs: jnp.ndarray) -> jnp.ndarray:
    """:func:`cdf_thresholds_int` of traced rows: (..., k) float32 -> (..., k-1) int32.

    The same 8-bit DAC: each tail sum ``P(value >= v)``, accumulated in
    float32 from the top value down, is rounded half-to-even onto the
    ``t / 256`` grid and clipped to [0, 256], then held non-increasing.
    """
    p = jnp.asarray(probs, jnp.float32)
    k = p.shape[-1]
    tails = [p[..., k - 1]]
    for v in range(k - 2, 0, -1):
        tails.append(tails[-1] + p[..., v])
    out, prev = [], None
    for tail in reversed(tails):
        t = jnp.clip(jnp.round(tail * 256.0), 0.0, 256.0).astype(jnp.int32)
        prev = t if prev is None else jnp.minimum(t, prev)
        out.append(prev)
    return jnp.stack(out, axis=-1)


def n_rand_words(n_bits: int) -> int:
    """uint32 entropy words needed for ``n_bits`` stream bits (word-padded)."""
    return bitops.n_words(n_bits) * RAND_WORDS_PER_OUT_WORD


def seed_words(key: jax.Array) -> jnp.ndarray:
    """Two uint32 seed words from a JAX PRNG key (typed or legacy uint32 pair)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.astype(jnp.uint32).reshape(-1)[:2]


_seed_words = seed_words


def _lowbias32(x: jnp.ndarray) -> jnp.ndarray:
    """Full-avalanche 32-bit integer hash (lowbias32), ~6 VPU ops per word."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def counter_iota(shape: tuple, offset=0) -> jnp.ndarray:
    """Row-major flattened counters of ``shape`` built from broadcasted iotas.

    Equals ``offset + arange(prod(shape)).reshape(shape)`` (mod 2^32) without
    ever materialising the flat 1-D intermediate -- each dimension contributes
    ``iota * stride`` directly at the output shape, so large-batch independent
    entropy never allocates a giant arange.  ``offset`` may be a Python int or
    a traced uint32 scalar (kernel tiles pass their global tile origin).
    """
    shape = tuple(int(d) for d in shape)
    off = jnp.asarray(offset, jnp.uint32) if not isinstance(offset, int) else \
        jnp.uint32(offset & 0xFFFFFFFF)
    if not shape:
        return off
    strides = []
    stride = 1
    for dim in reversed(shape):
        strides.append(stride)
        stride *= dim
    ctr = None
    for axis, s in enumerate(reversed(strides)):
        term = jax.lax.broadcasted_iota(jnp.uint32, shape, axis) * jnp.uint32(s & 0xFFFFFFFF)
        ctr = term if ctr is None else ctr + term
    return ctr + off


def counter_hash_words(
    key: jax.Array, shape: tuple, n_words: int, *, offset=0
) -> jnp.ndarray:
    """``shape + (n_words,)`` uint32 entropy via double-hashed counters.

    The decision hot path is entropy-bound, and Threefry's 20+ rounds dominate
    it; two rounds of the lowbias32 avalanche hash over a keyed counter give
    statistically clean stream entropy (means, pairwise correlation, and
    autocorrelation all within binomial noise at 2^14 bits -- asserted in
    tests) at a fraction of the cost.  Deterministic per key, like
    ``jax.random.bits``.  Not cryptographic -- neither is the memristor.

    ``offset`` shifts the counter block, so disjoint slices of one logical
    counter space can be drawn piecewise instead of generating (and slicing)
    the whole tensor.
    """
    kd = _seed_words(key)
    ctr = counter_iota(tuple(shape) + (n_words,), offset)
    return _lowbias32(_lowbias32(ctr ^ kd[0]) ^ kd[1])


# --- fused counter -> bit-plane entropy (the net_sweep generator) -----------------
#
# The fused whole-network sweep consumes entropy as *bit-planes*: for one packed
# output word, plane ``k`` is a uint32 word whose bit ``j`` is bit ``k`` of the
# 8-bit comparator byte at stream position ``j``.  Keeping the planes packed lets
# the byte-vs-threshold comparison run bit-sliced (a borrow chain over 8 words)
# with no byte extraction and no per-leaf packing.  Generation is two full
# lowbias32 avalanche rounds per plane word -- the same strength as
# ``counter_hash_words`` -- but the first round is shared by the 8 planes of an
# output word and the second round is salted per plane, so a 32-bit-stream word
# costs 1 + planes hashes instead of 2 x 8.

# Dense, well-spread odd salts (xxhash/murmur/splitmix finalizer constants);
# XORed into the second keyed round to separate the 8 bit-planes of one word.
PLANE_SALTS = (
    0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F, 0x165667B1,
    0x9E3779B9, 0xFF51AFD7, 0xC4CEB9FE, 0x2545F497,
)


def plane_base(ctr, kd0) -> jnp.ndarray:
    """First avalanche round over keyed counters, shared by a word's 8 planes."""
    return _lowbias32(jnp.asarray(ctr, jnp.uint32) ^ kd0)


def plane_word(base, kd1, plane: int) -> jnp.ndarray:
    """Second keyed round: one uint32 word of fair bits for bit-plane ``plane``."""
    return _lowbias32(base ^ jnp.uint32(PLANE_SALTS[plane]) ^ kd1)


def random_words(
    key: jax.Array, shape: tuple, n_bits: int, impl: str = "fast"
) -> jnp.ndarray:
    """Draw ``shape + (n_rand,)`` uint32 entropy words for ``n_bits``-bit streams.

    ``impl='fast'`` (default) uses the counter-hash generator;
    ``impl='threefry'`` uses ``jax.random.bits``.
    """
    if impl == "threefry":
        return jax.random.bits(key, tuple(shape) + (n_rand_words(n_bits),), jnp.uint32)
    return counter_hash_words(key, tuple(shape), n_rand_words(n_bits))


def packed_from_bytes(
    rand: jnp.ndarray,
    thresh: jnp.ndarray,
    flip: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Byte-threshold compare + in-register pack: the SNE comparator, packed.

    rand:   (..., n_rand) uint32 entropy, n_rand % 8 == 0.
    thresh: broadcastable to ``rand.shape[:-1]`` uint32 thresholds in [0, 256].
    flip:   optional bool mask (same broadcast) -- streams whose comparator is
            complemented (byte -> 255 - byte), the NOT-gate of the correlated
            encoder's negative mode (Fig S5b).

    Returns (..., n_rand // 8) uint32 packed streams.  Stream bit ``4r + b``
    comes from byte ``b`` of entropy word ``r``; it lands in output word
    ``r // 8`` at bit ``4 * (r % 8) + b`` (same layout as the Pallas kernel).
    """
    n_rand = rand.shape[-1]
    assert n_rand % RAND_WORDS_PER_OUT_WORD == 0
    n_out = n_rand // RAND_WORDS_PER_OUT_WORD
    thresh = jnp.asarray(thresh, jnp.uint32)[..., None]
    acc = jnp.zeros(jnp.broadcast_shapes(rand.shape[:-1], thresh.shape[:-1]) + (n_out,), jnp.uint32)
    for byte in range(BITS_PER_RAND_WORD):
        lane = (rand >> jnp.uint32(8 * byte)) & jnp.uint32(0xFF)
        if flip is not None:
            lane = jnp.where(flip[..., None], jnp.uint32(0xFF) - lane, lane)
        bits = (lane < thresh).astype(jnp.uint32)
        grouped = bits.reshape(bits.shape[:-1] + (bits.shape[-1] // 8, 8))
        shifts = (jnp.arange(8, dtype=jnp.uint32) * 4 + byte).astype(jnp.uint32)
        acc = acc | jnp.sum(grouped << shifts, axis=-1, dtype=jnp.uint32)
    return acc


def _mask_tail(words: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """Zero the pad bits when n_bits is not word-aligned (popcount invariant)."""
    if n_bits % bitops.WORD:
        return words & bitops.pad_mask(n_bits)
    return words


def encode_packed(
    key: jax.Array, p: jnp.ndarray, n_bits: int, impl: str = "fast"
) -> jnp.ndarray:
    """Independent packed Bernoulli streams: ``p.shape + (n_words,)`` uint32."""
    p = jnp.asarray(p, jnp.float32)
    rand = random_words(key, p.shape, n_bits, impl=impl)
    return _mask_tail(packed_from_bytes(rand, threshold_from_p(p)), n_bits)


def encode_packed_correlated(
    key: jax.Array,
    p: jnp.ndarray,
    n_bits: int,
    negate: jnp.ndarray | None = None,
    impl: str = "fast",
) -> jnp.ndarray:
    """Packed streams over the trailing axis of ``p`` sharing one entropy source.

    All streams in the group compare the *same* random bytes against their own
    threshold (one SNE, many comparator references): maximal positive
    correlation.  ``negate`` marks streams read through the complemented
    comparator: maximal negative correlation with the non-negated ones.
    """
    p = jnp.asarray(p, jnp.float32)
    rand = random_words(key, p.shape[:-1] + (1,), n_bits, impl=impl)
    flip = None if negate is None else jnp.asarray(negate, bool)
    return _mask_tail(packed_from_bytes(rand, threshold_from_p(p), flip), n_bits)


def encode_packed_categorical(
    key: jax.Array,
    cdf: tuple,
    n_bits: int,
    batch: int | None = None,
    impl: str = "fast",
) -> jnp.ndarray:
    """Categorical root sampling: one entropy byte -> ``value_bits(k)`` planes.

    cdf: static ``(k-1,)`` non-increasing cumulative thresholds in [0, 256]
    (:func:`cdf_thresholds_int`).  Draws the SAME entropy a binary
    :func:`encode_packed` of matching shape would (one byte per stream bit --
    the categorical draw is free after the first comparison), compares it
    against every threshold, and packs the sampled value's bit-planes.

    Returns ``(value_bits(k), n_words)`` uint32, or with a leading batch axis
    inserted after the plane axis when ``batch`` is given:
    ``(value_bits(k), batch, n_words)``.
    """
    lead = () if batch is None else (batch,)
    rand = random_words(key, lead, n_bits, impl=impl)
    levels = [
        packed_from_bytes(rand, jnp.uint32(t)) for t in cdf
    ]
    planes = bitops.value_planes(levels)
    return jnp.stack([_mask_tail(p, n_bits) for p in planes])


def fair_bits(key: jax.Array, shape: tuple, n_bits: int, impl: str = "fast") -> jnp.ndarray:
    """p = 0.5 packed streams straight from the generator (1 entropy bit/stream bit).

    MUX-tree selects are always fair coins; drawing the packed words directly
    skips even the byte comparison.  Pad bits are zeroed as usual.
    ``impl='threefry'`` draws the words from ``jax.random.bits`` instead of the
    counter-hash generator, so threefry mode stays end-to-end reproducible
    against other JAX code (the flag used to be silently unavailable here,
    which broke reproducibility for any circuit with a MUX-tree select).
    """
    if impl == "threefry":
        words = jax.random.bits(key, tuple(shape) + (bitops.n_words(n_bits),), jnp.uint32)
    else:
        words = counter_hash_words(key, tuple(shape), bitops.n_words(n_bits))
    return _mask_tail(words, n_bits)
