"""Backend dispatch shared by all Pallas kernels.

Every kernel wrapper used to hardcode ``interpret=True`` -- correct on CPU,
but it silently ran the Pallas *interpreter* on real TPU/GPU backends, turning
the kernels into demos.  This module centralises the decision:

* ``interpret=None`` (the default everywhere) -> auto-detect: compile the
  kernel on TPU/GPU, fall back to interpret mode only when the default JAX
  backend is CPU (where Mosaic cannot lower).
* ``interpret=True`` / ``False`` -> explicit override, e.g. tests that pin
  interpret mode for determinism, or benchmarks probing both paths.

Block-size choice and the Mosaic-safe argmax are also shared here so the
per-kernel wrappers stay thin, and so is the persistent compilation cache
that entry points switch on.
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import jax.numpy as jnp

# src/repro/kernels/backend.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """True only when the default JAX backend cannot compile Pallas (CPU)."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the tri-state ``interpret`` flag to a concrete bool."""
    if interpret is None:
        return default_interpret()
    return bool(interpret)


def resolve_use_kernel(use_kernel: bool | None, interpret: bool) -> bool:
    """Resolve ``use_kernel=None``: run the Pallas kernel only where it compiles.

    The interpreter exists to validate kernels against their oracles, not to
    serve traffic -- when the resolved mode is interpret (CPU fallback), the
    production default is the pure-jnp reference, which XLA fuses natively.
    """
    if use_kernel is None:
        return not interpret
    return bool(use_kernel)


def pick_block(rows: int, preferred: int) -> int:
    """Largest block size from the standard ladder that tiles ``rows`` exactly.

    Every rung is a multiple of 8, and when none divides ``rows`` the block
    is ``rows`` itself: Mosaic accepts a block dimension only when it is a
    multiple of the (8, 128) tile or spans the whole array dimension.
    """
    for cand in (preferred, 256, 128, 64, 32, 8):
        if cand <= rows and rows % cand == 0:
            return cand
    return rows


def first_argmax(counts: jnp.ndarray) -> jnp.ndarray:
    """``jnp.argmax(counts, axis=-1)`` (first occurrence wins) from max, iota
    and min: the form Mosaic lowers for int32 inside a kernel, which
    ``argmax`` is not."""
    best = jnp.max(counts, axis=-1, keepdims=True)
    idx = jax.lax.broadcasted_iota(jnp.int32, counts.shape, counts.ndim - 1)
    return jnp.min(
        jnp.where(counts == best, idx, jnp.int32(counts.shape[-1])), axis=-1
    )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when it is set, else
    ``.jax_cache/`` at the root of the checkout: a fixed path, since the path
    is part of the key.  Called by entry points (``chip_smoke.py``, the
    benchmark and serving launchers), never on import: tests and library
    users keep JAX's default.  Every program is cached, however short its
    compile: a cold call compiles each scenario x bucket x stream length once.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
