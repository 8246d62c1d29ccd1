"""Pallas TPU kernel: fused M-way packed AND + SWAR popcount.

This is the Bayes-fusion numerator (eq (5) product) evaluated on packed
stochastic numbers: the AND chain and the popcount reduction run in one VMEM
pass, so the intermediate bitstreams never touch HBM -- the TPU analogue of the
paper's claim that the SC operator avoids pre-/post-processing circuitry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pand_kernel(streams_ref, out_ref):
    s = streams_ref[...]                       # (M, bR, n_words) u32
    acc = s[0]
    for i in range(1, s.shape[0]):
        acc = acc & s[i]
    x = acc
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    counts = (x * jnp.uint32(0x01010101)) >> 24
    out_ref[...] = jnp.sum(counts.astype(jnp.int32), axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def pand_popcount_pallas(
    streams: jnp.ndarray, *, block_r: int = 512, interpret: bool = True
) -> jnp.ndarray:
    """streams: (M, R, n_words) uint32 -> (R,) int32 fused AND+popcount."""
    m, r, n_words = streams.shape
    block_r = min(block_r, r)
    assert r % block_r == 0, f"rows {r} not divisible by block {block_r}"
    grid = (r // block_r,)
    # counts leave as an (R, 1) column: Mosaic tiles a rank-1 block
    # differently from XLA's layout of the (R,) array
    return pl.pallas_call(
        _pand_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((m, block_r, n_words), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
    )(streams)[:, 0]
