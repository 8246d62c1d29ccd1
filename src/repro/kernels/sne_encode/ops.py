"""jit'd public wrapper for the SNE encode kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import rng
from repro.kernels import backend
from repro.kernels.sne_encode.kernel import sne_encode_pallas
from repro.kernels.sne_encode.ref import sne_encode_ref


@functools.partial(jax.jit, static_argnames=("n_bits", "use_kernel", "interpret"))
def sne_encode(
    key: jax.Array,
    p: jnp.ndarray,
    n_bits: int = 128,
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Encode probabilities ``p`` (any shape) into packed stochastic numbers.

    n_bits must be a multiple of 32.  Returns ``p.shape + (n_bits // 32,)`` uint32.
    Entropy is drawn from the counter-based PRNG (the TPU stand-in for the
    memristor's stochastic V_th; see DESIGN.md SS2) -- on real TPUs this becomes
    in-kernel ``pltpu.prng_random_bits`` with identical semantics.
    ``interpret=None`` auto-detects the backend (compiled on TPU/GPU,
    interpreter only as CPU fallback).
    """
    assert n_bits % 32 == 0, "kernel path packs whole uint32 words"
    interpret = backend.resolve_interpret(interpret)
    # the TPU compiler (Mosaic) refuses this kernel (float32 -> uint32 casts,
    # uint32 reductions): the bit-exact reference is the default everywhere
    use_kernel = bool(use_kernel)
    p = jnp.asarray(p, jnp.float32)
    flat = p.reshape(-1)
    n_rand = n_bits // 4  # 4 bytes (stream bits) per random word
    rand = rng.counter_hash_words(key, (flat.shape[0],), n_rand)
    if use_kernel:
        block = backend.pick_block(flat.shape[0], 256)
        out = sne_encode_pallas(flat, rand, block_r=block, interpret=interpret)
    else:
        out = sne_encode_ref(flat, rand)
    return out.reshape(p.shape + (n_bits // 32,))
