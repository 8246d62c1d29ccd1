"""jit'd public wrapper for the fused whole-network sweep.

``net_sweep`` lowers an entire compiled Bayesian network -- every node's
threshold-gather sample, the evidence-indicator AND, and the CORDIV popcount
fixed point -- into one backend-dispatched launch.  Entropy is generated
in-register from counter bit-planes (``rng.plane_base`` / ``rng.plane_word``),
so the ``share_entropy=False`` production mode stops writing
``B x nodes x 2**m x n_rand`` words to HBM per launch: nothing but the
evidence frames goes in and nothing but the per-frame counts comes out.

Two optional extensions make the launch span further:

* ``frame0`` / ``total_frames`` place the call inside a larger logical batch.
  The entropy counter is a pure function of the global (node, frame, word)
  index, so a shard that passes its global frame origin and the global frame
  count produces bit-identical words to the single-device sweep over its
  slice -- this is what ``compile_network(devices=...)`` wraps in
  ``shard_map``.
* ``decide=True`` appends the decision epilogue: per-query count vectors
  argmaxed in-register (``common.decide_counts``), returning
  ``(numer, denom, decisions)`` so the sense->classify->act path is one
  launch with no posterior re-encode.

Dispatch follows the other kernel ops: Pallas kernel where it compiles,
bit-exact jnp reference (the same ``sweep_tile`` body over the whole array) as
the CPU production fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import rng
from repro.kernels import backend
from repro.kernels.net_sweep.common import SweepPlan
from repro.kernels.net_sweep.kernel import net_sweep_pallas
from repro.kernels.net_sweep.ref import net_sweep_ref


@functools.partial(
    jax.jit,
    static_argnames=(
        "plan", "n_bits", "total_frames", "decide", "use_kernel", "interpret",
    ),
)
def net_sweep(
    key: jax.Array,
    ev_frames: jnp.ndarray,
    *,
    plan: SweepPlan,
    n_bits: int = 4096,
    frame0=0,
    total_frames: int | None = None,
    decide: bool = False,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """Run the fused sweep: per-frame independent joint samples, conditioned.

    ev_frames: (B, n_ev) int32 evidence values (one integer in ``[0, card)``
    per evidence node), columns in ``plan.evidence`` order; a plan with a
    tracked ``state`` takes ``plan.n_state_cols`` more columns, the state
    root's per-frame DAC thresholds.  Returns
    ``(numer (B, n_value_slots) int32, denom (B,) int32)``: one CORDIV ratio
    numerator popcount per query *value* (queries in plan order, values
    ``1 .. card-1`` within a query; the value-0 count is ``denom`` minus the
    query's slots) and the accepted-bit count per frame
    (``posterior ~ numer / denom``, noise ``~ sqrt(p (1-p) / denom)``).  For
    an all-binary plan this is exactly the old one-column-per-query layout.
    With ``decide=True`` a third array ``(B, n_q) int32`` of per-query argmax
    values is appended -- bit-identical to argmaxing the posterior, computed
    from the same in-register counts.

    Every frame draws an independent joint sample (the frame index is folded
    into the entropy counters), which is what the physical memristor array
    provides for free -- the fused path makes it the cheap mode instead of a
    ``B x`` penalty.  ``frame0`` (int or traced uint32 scalar) and
    ``total_frames`` (static int) let a shard of a larger launch draw the
    global batch's entropy for its frame slice.
    """
    if n_bits % 32:
        raise ValueError("n_bits must be a multiple of 32 (packed words)")
    interpret = backend.resolve_interpret(interpret)
    use_kernel = backend.resolve_use_kernel(use_kernel, interpret)
    ev = jnp.asarray(ev_frames, jnp.int32)
    assert ev.ndim == 2 and ev.shape[1] == len(plan.evidence) + plan.n_state_cols, (
        ev.shape, plan.evidence, plan.n_state_cols,
    )
    kd = rng.seed_words(key)
    if use_kernel:
        # zero-width blocks are not representable; pad the (unused) ev input
        ev_k = ev if ev.shape[1] else jnp.zeros((ev.shape[0], 1), jnp.int32)
        block_f = backend.pick_block(ev.shape[0], 128)
        block_w = backend.pick_block(n_bits // 32, 256)
        return net_sweep_pallas(
            kd, ev_k, plan=plan, n_bits=n_bits,
            frame0=frame0, total_frames=total_frames, decide=decide,
            block_f=block_f, block_w=block_w, interpret=interpret,
        )
    return net_sweep_ref(kd, ev, plan, n_bits, frame0=frame0,
                         total_frames=total_frames, decide=decide)
