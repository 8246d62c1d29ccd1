"""Pallas TPU kernel: fused batched Bayes decision (encode -> AND -> popcount -> argmax).

One VMEM pass over the whole decision: the SNE byte-threshold comparison
(encode), the M-way AND across modalities (eq (5) numerator product), the
stream popcount, and the K-way argmax all happen on registers -- no packed
stream, no per-bit tensor, and no intermediate ever touches HBM.  Because the
AND-of-comparisons is consumed immediately by the count, the kernel never even
materialises the packed words the unfused pipeline ships between its three
launches (DESIGN.md SS7).

Entropy is passed in as pre-drawn counter-based uint32 words (4 uniform bytes
per word, same scheme as ``kernels/sne_encode``), keeping the kernel
deterministic and bit-exact against the jnp oracle in ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.rng import threshold_from_p
from repro.kernels.backend import first_argmax


def _decide_kernel(p_ref, rand_ref, dec_ref, cnt_ref):
    p = p_ref[...]                        # (M, bR, K) f32
    rand = rand_ref[...]                  # (M, bR, K, n_rand) u32
    thresh = threshold_from_p(p)
    m = rand.shape[0]
    total = jnp.zeros(rand.shape[1:3], jnp.int32)          # (bR, K)
    for byte in range(4):
        lane = (rand >> jnp.uint32(8 * byte)) & jnp.uint32(0xFF)
        bits = lane < thresh[..., None]                    # (M, bR, K, n_rand)
        joint = bits[0]
        for i in range(1, m):
            joint = joint & bits[i]
        total = total + jnp.sum(joint.astype(jnp.int32), axis=-1)
    cnt_ref[...] = total
    # first-occurrence argmax via iota+min (lowers on Mosaic, unlike argmax)
    dec_ref[...] = first_argmax(total)[:, None]


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def bayes_decide_pallas(
    p: jnp.ndarray,
    rand_words: jnp.ndarray,
    *,
    block_r: int = 256,
    interpret: bool = False,
):
    """p: (M, R, K) f32; rand_words: (M, R, K, n_rand) u32.

    Returns (decisions (R,) int32, counts (R, K) int32).
    """
    m, r, k, n_rand = rand_words.shape
    assert p.shape == (m, r, k)
    block_r = min(block_r, r)
    assert r % block_r == 0, f"rows {r} not divisible by block {block_r}"
    grid = (r // block_r,)
    # decisions leave as an (R, 1) column: Mosaic tiles a rank-1 block
    # differently from XLA's layout of the (R,) array
    dec, counts = pl.pallas_call(
        _decide_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_r, k), lambda i: (0, i, 0)),
            pl.BlockSpec((m, block_r, k, n_rand), lambda i: (0, i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, 1), jnp.int32),
            jax.ShapeDtypeStruct((r, k), jnp.int32),
        ],
        interpret=interpret,
    )(p, rand_words)
    return dec[:, 0], counts
