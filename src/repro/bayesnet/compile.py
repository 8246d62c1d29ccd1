"""Compile a :class:`~repro.bayesnet.spec.NetworkSpec` to the packed domain.

Nodes are cardinality-``k`` categorical variables carried as ``value_bits(k)``
packed bit-plane streams (binary = the one-plane ``k=2`` special case, bit
identical to the pre-categorical lowering).  Two lowerings share the spec
language:

**Fused** (production default for independent entropy): the whole network --
per-node categorical threshold-gather sampling, evidence-indicator AND, CORDIV
popcount fixed point -- becomes ONE :func:`~repro.kernels.net_sweep.net_sweep`
launch.  Entropy is generated in-register from counter bit-planes with the
frame index folded into the counters (ONE byte per stream position regardless
of cardinality), so every frame draws an independent joint sample and node
streams never touch HBM.

**Unfused** (one op per node; the verification baseline, and the only path
for shared entropy or the ``fill`` estimator):

* binary roots     -> independent packed Bernoulli streams (``rng.encode_packed``).
* k-ary roots      -> ``rng.encode_packed_categorical`` (same entropy words,
  ``k-1`` comparisons, ``value_bits(k)`` planes).
* all-binary nodes -> the :func:`~repro.kernels.node_mux.node_mux` sweep
  (``mux_mode='gather'`` default; ``mux_mode='rows'`` is the original
  formulation kept as the binary statistical baseline).
* k-ary nodes (or binary nodes with k-ary parents)
                   -> :func:`~repro.kernels.node_mux.node_mux_categorical`:
  the parents' value digits gather the row's 8-bit DAC CDF, one entropy byte
  samples the k-way draw.
* queries          -> stochastic conditioning: per-evidence-node value
  indicators (AND of plane literals) are ANDed into the acceptance stream
  ``d``; each query *value* indicator ANDed with ``d`` is a bitwise subset of
  ``d`` by construction, so CORDIV's correlation discipline holds.
  ``estimator='ratio'`` uses the closed-form popcount fixed point;
  ``estimator='fill'`` runs the word-parallel ``cordiv_fill`` flip-flop
  circuit per value slot.

Posterior contract: when every query node is binary, ``run`` returns the
classic ``(B, n_q)`` array of ``P(q=1 | evidence)`` -- bit-identical to the
pre-categorical compiler.  When any query has ``k > 2``, ``run`` returns a
``(B, n_q, max_k)`` tensor of normalised per-value posteriors (rows of
queries with smaller cardinality are zero-padded).  ``decide`` returns the
posterior AND its per-query MAP decisions from the same launch: the fused
path argmaxes the count slots in-register (``net_sweep``'s decision
epilogue), the unfused path argmaxes the assembled posterior -- identical
results by construction.

``compile_network(devices=N)`` (or an ambient ``mesh_context``) shards the
fused launch over the frame axis with ``shard_map``; the global frame index
is folded into the per-frame entropy counters, so sharded output is
bit-identical to single-device output on every scenario.

**Tracked networks.**  A spec with a ``state`` (a root carried from frame to
frame, :mod:`repro.bayesnet.spec`) compiles to a fused program whose ``run``
and ``decide`` also take a belief table held on the device -- ``max_tracks x
k`` float32, one row per live track (:meth:`CompiledNetwork.new_table`) --
with each frame's row index and a fresh-track flag.  Inside the one jitted
call the program gathers the frames' rows, predicts each frame's prior (the
published prior for a fresh track, ``belief @ transition`` otherwise,
float32), quantises it with the same 8-bit DAC as every CPT row, sweeps with
those thresholds in place of the root's CPT row, and scatters each frame's
posterior over the state back into its row (the prediction it used, when it
accepted no sample).  The table is donated and returned.  A launch holds at
most one frame of a track; rows past the table (the driver's padding lanes)
read the published prior and write nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Callable, Iterator, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.bayesnet.noise import NoiseModel, perturbed_cdf_rows
from repro.bayesnet.spec import NetworkSpec
from repro.core import bitops, cordiv, rng
from repro.distributed import context as dist_context
from repro.distributed import sharding as dist_sharding
from repro.kernels.net_sweep import SweepPlan, net_sweep
from repro.kernels.node_mux.ops import node_mux, node_mux_categorical
from repro.obs import Tracer


def network_stats(net: "CompiledNetwork") -> dict:
    """Static plan statistics for one compiled program (span / log fodder).

    * ``n_nodes`` / ``n_edges``: DAG shape.
    * ``cpt_rows``: total CPT rows lowered (one per parent assignment per
      node) -- the crossbar row count of the modelled array.
    * ``n_thresholds``: total 8-bit DAC comparator thresholds
      (``rows x (card - 1)`` per node), the quantity the noise model perturbs.
    * ``threshold_mask_bytes``: size of the trace-time-folded comparator
      constants in the fused sweep -- each threshold contributes 8 bit-plane
      mask words of 4 bytes (:mod:`repro.kernels.net_sweep`'s borrow-chain
      literals), so this is the plan's constant footprint, the number that
      grows when a network deepens.
    * ``n_value_slots``: numerator count slots (``card - 1`` per query).
    """
    spec = net.spec
    n_edges = n_rows = n_thresholds = 0
    for name in spec.topo_order():
        node = spec.node(name)
        rows = spec.cpt_rows(name)
        n_edges += len(node.parents)
        n_rows += len(rows)
        n_thresholds += len(rows) * (spec.card(name) - 1)
    return {
        "n_nodes": spec.n_nodes,
        "n_edges": n_edges,
        "cpt_rows": n_rows,
        "n_thresholds": n_thresholds,
        "threshold_mask_bytes": n_thresholds * 8 * 4,
        "n_value_slots": sum(c - 1 for c in net.query_cards),
        "n_bits": net.n_bits,
        "fused": net.fused,
        "n_shards": net.n_shards,
    }


def _posterior_from_counts(numer: jnp.ndarray, denom: jnp.ndarray) -> jnp.ndarray:
    """Per-frame posteriors from count arrays: numer (B, n_s), denom (B,)."""
    return cordiv.ratio_from_counts(numer, denom[:, None])


def _slot_assembler(q_cards: Tuple[int, ...]) -> Callable:
    """Build the slot-probabilities -> posterior map for a query card profile.

    Slots hold ``P(q = v | e)`` for values ``1 .. k-1`` per query, in query
    order.  All-binary queries keep the classic ``(B, n_q)`` layout (the slot
    array IS the posterior, bit-identical to the pre-categorical path);
    otherwise the slots fold into ``(B, n_q, max_k)`` with
    ``P(q = 0) = 1 - sum`` and zero padding past each query's cardinality.
    Used by the ``fill`` estimator, whose slots are independent stochastic
    divisions with no underlying integer counts; the ratio paths assemble
    from counts instead (:func:`_count_assembler`).
    """
    if all(c == 2 for c in q_cards):
        return lambda slots: slots
    kmax = max(q_cards)

    def assemble(slots: jnp.ndarray) -> jnp.ndarray:
        cols = []
        off = 0
        for c in q_cards:
            v = slots[:, off : off + c - 1]
            off += c - 1
            s = jnp.sum(v, axis=-1, keepdims=True)
            p0 = jnp.clip(1.0 - s, 0.0, 1.0)
            parts = [p0, v]
            if kmax > c:
                parts.append(jnp.zeros(v.shape[:-1] + (kmax - c,), v.dtype))
            # Ratio-estimator slots are disjoint-bucket count fractions, so
            # s <= 1 exactly and the divisor is literally 1.0; the fill
            # estimator's slots are independent stochastic divisions whose
            # noise can push s past 1 -- rescale so the vector stays a
            # distribution either way.
            cols.append(jnp.concatenate(parts, axis=-1) / jnp.maximum(s, 1.0))
        return jnp.stack(cols, axis=1)

    return assemble


def _count_assembler(q_cards: Tuple[int, ...]) -> Callable:
    """Counts -> posterior map for the ratio paths (count-exact value 0).

    Same layout as :func:`_slot_assembler` -- all-binary query sets keep the
    classic ``(B, n_q)`` slot array bit-identically -- but every k-ary column
    is the correctly-rounded float32 of ``count / denom``, with the value-0
    count reconstructed in the *integer* domain (``denom - sum(slots)``), the
    SAME convention :func:`~repro.kernels.net_sweep.decide_counts` applies
    before its argmax (the two must stay in lockstep or the fused decisions
    and posterior diverge).  ``1 - sum(float slots)`` can land one ULP below
    a tied slot probability, which would flip the posterior argmax away from
    the count argmax on exact count ties; dividing the integer counts instead
    makes equal counts equal floats, so the decide epilogue's tie-break
    (lowest value) and the posterior argmax agree on every input by
    construction.  A ``denom == 0`` frame yields the all-zero vector (the
    :func:`ratio_from_counts` convention the binary path already follows).
    """
    if all(c == 2 for c in q_cards):
        return lambda numer, denom: _posterior_from_counts(numer, denom)
    kmax = max(q_cards)

    def assemble(numer: jnp.ndarray, denom: jnp.ndarray) -> jnp.ndarray:
        cols = []
        off = 0
        for c in q_cards:
            v = numer[:, off : off + c - 1]
            off += c - 1
            c0 = denom[:, None] - jnp.sum(v, axis=-1, keepdims=True)
            counts = jnp.concatenate([c0, v], axis=-1)
            p = cordiv.ratio_from_counts(counts, denom[:, None])
            if kmax > c:
                p = jnp.concatenate(
                    [p, jnp.zeros((p.shape[0], kmax - c), p.dtype)], axis=-1
                )
            cols.append(p)
        return jnp.stack(cols, axis=1)

    return assemble


def _array_module(x):
    """``jnp`` for a device array or a tracer, ``np`` for anything on the host."""
    return jnp if isinstance(x, jax.Array) else np


def _start_host_copies(arrays) -> int:
    """Start each concrete array's device -> host copy; returns how many.

    ``copy_to_host_async`` enqueues the copy of every addressable shard and
    returns at once; a later ``np.asarray`` reads the copied value.  Tracers
    (a call under ``jit`` / ``vmap``) have no device buffer and are skipped.
    """
    n = 0
    for x in arrays:
        if not isinstance(x, jax.core.Tracer):
            x.copy_to_host_async()
            n += 1
    return n


def posterior_argmax(post: jnp.ndarray) -> jnp.ndarray:
    """MAP decision from a ``run`` posterior, matching the fused epilogue.

    Binary layout ``(B, n_q)``: value 1 wins iff ``P(q=1) > 0.5`` (exactly
    ``argmax([1-p, p])`` with ties to value 0).  k-ary layout
    ``(B, n_q, kmax)``: argmax over the value axis (ties to the lowest value,
    zero padding past a query's cardinality can never win).  This is the same
    tie-break :func:`~repro.kernels.net_sweep.decide_counts` applies to the
    raw counts, and the ratio-estimator posteriors (fused and unfused) are
    assembled count-exactly (:func:`_count_assembler`: equal counts -> equal
    floats), so applying this to a fused ``run`` posterior reproduces the
    in-kernel decisions bit-for-bit.  Only the ``fill`` estimator's
    posterior, which has no integer counts underneath, can land float ties
    off the count grid.
    """
    post = jnp.asarray(post)
    if post.ndim == 2:
        return (post > 0.5).astype(jnp.int32)
    return jnp.argmax(post, axis=-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class CompiledNetwork:
    """A network lowered to one jitted packed-stochastic program.

    ``run(key, ev_frames (B, n_ev) int) -> (post, accepted (B,))``: evidence
    values are integers in ``[0, card)`` per evidence node.  ``post`` is
    ``(B, n_q)`` of ``P(q=1 | evidence)`` when every query is binary, else
    ``(B, n_q, max(query_cards))`` of normalised per-value posteriors.
    ``accepted[b]`` is the number of stream positions that satisfied frame
    ``b``'s evidence -- the effective sample count, so callers can bound the
    noise as ``sigma ~ sqrt(p (1-p) / accepted)``.

    ``n_shards > 1`` marks the sharded fused program: one ``shard_map``
    launch spans ``n_shards`` devices over the frame axis (``shard_axes``),
    bit-identical to the single-device program for any batch the shard count
    divides (indivisible batches transparently run the single-device path).

    ``trace`` (a :class:`~repro.obs.Tracer`, set by ``compile_network(trace=)``)
    makes every ``run`` / ``decide`` call record its span tree (see
    :meth:`decide`); ``None`` leaves both calls untouched.

    A tracked network (``spec.state`` set) takes three more arguments,
    ``table`` (``new_table(max_tracks)``, donated), ``track_idx`` (B,) int
    and ``fresh`` (B,) bool, and returns the updated table last:
    ``run -> (post, accepted, table)``, ``decide -> (post, decisions,
    accepted, table)``.
    """

    spec: NetworkSpec
    queries: Tuple[str, ...]
    evidence: Tuple[str, ...]
    n_bits: int
    share_entropy: bool
    estimator: str
    fused: bool
    query_cards: Tuple[int, ...]
    _run: Callable = dataclasses.field(repr=False)
    _decide: Callable = dataclasses.field(repr=False)
    n_shards: int = 1
    shard_axes: Tuple[str, ...] = ()
    noise: NoiseModel | None = None
    # Within-launch drift epochs baked into the plan (1 = frozen snapshot)
    # and the programmed-threshold override the plan was lowered from
    # (calibrate-back compensation; None = clean spec thresholds).
    drift_epochs: int = 1
    program: dict | None = dataclasses.field(default=None, repr=False, compare=False)
    trace: Tracer | None = dataclasses.field(default=None, repr=False, compare=False)
    # per-network call index of traced calls (the ``launch`` span attr)
    _calls: Iterator[int] = dataclasses.field(
        default_factory=itertools.count, init=False, repr=False, compare=False
    )

    def _check_frames(self, ev_frames, xp=jnp) -> jnp.ndarray:
        ev = xp.asarray(ev_frames, xp.int32)
        if ev.ndim != 2 or ev.shape[1] != len(self.evidence):
            raise ValueError(
                f"evidence frames must be (B, {len(self.evidence)}), got {ev.shape}"
            )
        return ev

    @property
    def tracked(self) -> bool:
        """True when the network carries a state root from frame to frame."""
        return self.spec.state is not None

    def new_table(self, max_tracks: int) -> jax.Array:
        """An empty belief table: ``max_tracks`` rows of the state's ``k`` values.

        Rows are read only by frames of continuing tracks; a track's first
        frame (``fresh``) starts from the published prior and fills its row.
        """
        if not self.tracked:
            raise ValueError(f"{self.spec.name}: an untracked network has no belief table")
        if max_tracks < 1:
            raise ValueError(f"max_tracks must be >= 1, got {max_tracks}")
        return jnp.zeros((int(max_tracks), self.spec.card(self.spec.state_node)), jnp.float32)

    def _args(self, ev_frames, table, track_idx, fresh) -> tuple:
        """The jitted program's arguments after the key, checked."""
        if not self.tracked:
            if table is not None or track_idx is not None or fresh is not None:
                raise ValueError(f"{self.spec.name}: an untracked network takes no track state")
            return (self._check_frames(ev_frames),)
        if table is None or track_idx is None or fresh is None:
            raise ValueError(
                f"{self.spec.name}: a tracked network needs table, track_idx and fresh"
            )
        # host inputs stay host arrays: the jitted call moves them to the
        # device in its own dispatch, where a device put of each costs a
        # transfer of its own; device arrays and tracers stay as they are
        ev = self._check_frames(ev_frames, _array_module(ev_frames))
        idx = _array_module(track_idx).asarray(track_idx, np.int32)
        new = _array_module(fresh).asarray(fresh, np.bool_)
        if idx.shape != (ev.shape[0],) or new.shape != (ev.shape[0],):
            raise ValueError(
                f"track_idx {idx.shape} and fresh {new.shape} need one entry per frame "
                f"({ev.shape[0]})"
            )
        return (ev, table, idx, new)

    def _traced_call(
        self, name: str, fn: Callable, key: jax.Array, ev_frames, tracks=(None,) * 3,
        host_copies: int = 0,
    ):
        """One call of ``fn`` under a ``name`` span with two sync children.

        ``<name>.evidence`` covers the host evidence -> device array
        conversion, ``<name>.dispatch`` the jitted call (enqueue only, under
        async dispatch; its ``compiled`` attr is true when the call grew the
        jit's cache, which also adds 1 to the tracer's ``<name>.compiles``
        count).  The first ``host_copies`` outputs' device -> host copies are
        started after ``<name>.dispatch`` closes, inside the parent span, and
        their number is added to the ``<name>.host_copies`` count.  The
        parent's attrs are counts at the layer boundary taken from shapes,
        never from a device sync.
        """
        tr = self.trace
        with tr.span(name, network=self.spec.name, launch=next(self._calls)) as sp:
            with tr.span(f"{name}.evidence"):
                args = self._args(ev_frames, *tracks)
            ev = args[0]
            n_cached = fn._cache_size()
            with tr.span(f"{name}.dispatch") as dsp:
                out = fn(key, *args)
            compiled = fn._cache_size() > n_cached
            dsp.attrs["compiled"] = compiled
            if compiled:
                tr.inc(f"{name}.compiles")
            if host_copies:
                tr.inc(f"{name}.host_copies", _start_host_copies(out[:host_copies]))
            sp.attrs.update(
                frames=ev.shape[0], in_bytes=ev.nbytes + key.nbytes,
                out_arrays=len(out), out_bytes=sum(x.nbytes for x in out),
            )
        return out

    def run(self, key: jax.Array, ev_frames, table=None, track_idx=None, fresh=None):
        """Posteriors and accepted counts of one launch: ``(post, accepted)``.

        A tracked network's ``run`` returns its table last and, as ``decide``
        does, starts the device -> host copies of ``post`` and ``accepted``
        before it returns (``run.host_copies``, 2 a call, when traced): its
        driver fetches both of every launch, and the table stays on the
        device.  An untracked ``run`` starts no copies.
        """
        tracks = (table, track_idx, fresh)
        copies = 2 if self.tracked else 0
        if self.trace is None:
            out = self._run(key, *self._args(ev_frames, *tracks))
            if copies:
                _start_host_copies(out[:copies])
            return out
        return self._traced_call("run", self._run, key, ev_frames, tracks,
                                 host_copies=copies)

    def decide(self, key: jax.Array, ev_frames, table=None, track_idx=None, fresh=None):
        """Posteriors AND per-query MAP decisions in the same launch.

        Fused programs run ``net_sweep(..., decide=True)``: the decision
        epilogue argmaxes the per-query count slots in-register, so the whole
        sense->classify->act path is one launch -- no posterior re-encode, no
        second kernel.  Unfused programs argmax the assembled posterior
        (:func:`posterior_argmax`); both produce the decision a MAP readout
        of ``run``'s posterior would, bit-for-bit.  Returns
        ``(post, decisions (B, n_q) int32, accepted)``.

        The three outputs come back as ``jax.Array``s without waiting on the
        device, with their device -> host copies already started: a caller's
        ``np.asarray`` finds each copy in flight or done instead of starting
        it, so the copies overlap one another and the caller's own work.
        Outputs of a call made under a JAX transformation are tracers and are
        returned untouched.

        With ``trace`` set, each call records a ``decide`` span (attrs
        ``network``, ``frames``, ``launch``, ``in_bytes``, ``out_arrays``,
        ``out_bytes``) over ``decide.evidence`` and ``decide.dispatch``
        children, counts recompiles in ``decide.compiles`` and the copies it
        started in ``decide.host_copies`` (3 a call); ``run`` records the same
        tree under ``run`` (see :meth:`run` for its copies).  The outputs are
        bit-identical.  A tracked network's table comes back last, left on
        the device.
        """
        tracks = (table, track_idx, fresh)
        if self.trace is None:
            out = self._decide(key, *self._args(ev_frames, *tracks))
            _start_host_copies(out[:3])
            return out
        return self._traced_call(
            "decide", self._decide, key, ev_frames, tracks, host_copies=3
        )


def sweep_plan(
    spec: NetworkSpec,
    queries: Sequence[str],
    evidence: Sequence[str],
    noise: NoiseModel | None = None,
    *,
    drift_epochs: int = 1,
    program: dict | None = None,
) -> SweepPlan:
    """Lower a spec to the static :class:`SweepPlan` the fused kernel consumes.

    Nodes are renumbered into topological order; each CPT row becomes its
    ``card - 1`` cumulative 8-bit DAC comparator thresholds
    (``rng.cdf_thresholds_int`` -- for binary nodes exactly the old
    ``round(p * 256)`` grid), so the fused sweep samples the identical
    quantised network every other encoder does.  ``noise`` perturbs every
    threshold through the crossbar non-ideality model
    (:mod:`repro.bayesnet.noise`) before it is baked into the plan --
    ``noise=None`` produces exactly the clean plan.

    ``drift_epochs=E > 1`` models the read-noise snapshot advancing *within*
    one launch: epoch ``e`` re-perturbs the thresholds at
    ``noise.with_cycle(noise.cycle + e)`` and the sweep applies each epoch's
    rows to its share of the word axis (:func:`~repro.kernels.net_sweep.common.
    epoch_word_bounds`).  ``drift_epochs=1`` produces exactly the
    single-snapshot plan.  ``program`` overrides the programmed thresholds
    fed into the perturbation (calibrate-back compensation, see
    :func:`~repro.bayesnet.noise.perturbed_cdf_rows`).  A tracked spec's
    state root becomes the plan's ``state``: its thresholds then come per
    frame, and its CPT row (the published prior) is what fresh tracks use.
    """
    drift_epochs = int(drift_epochs)
    if drift_epochs > 1 and noise is None:
        raise ValueError("drift_epochs > 1 needs a NoiseModel to advance")
    order = spec.topo_order()
    index = {name: i for i, name in enumerate(order)}
    perturbed = (
        perturbed_cdf_rows(spec, noise, program=program)
        if noise is not None or program is not None else None
    )
    nodes = []
    for name in order:
        node = spec.node(name)
        if perturbed is not None:
            rows = perturbed[name]
        else:
            rows = tuple(rng.cdf_thresholds_int(r) for r in spec.cpt_rows(name))
        nodes.append((tuple(index[p] for p in node.parents), spec.card(name), rows))
    epoch_rows = []
    for e in range(1, drift_epochs):
        pe = perturbed_cdf_rows(
            spec, noise.with_cycle(noise.cycle + e), program=program
        )
        epoch_rows.append(tuple(pe[name] for name in order))
    return SweepPlan(
        nodes=tuple(nodes),
        evidence=tuple(index[e] for e in evidence),
        queries=tuple(index[q] for q in queries),
        epochs=drift_epochs,
        epoch_rows=tuple(epoch_rows),
        state=None if spec.state is None else index[spec.state_node],
    )


def lower_streams(
    spec: NetworkSpec,
    key: jax.Array,
    n_bits: int,
    batch: int | None = None,
    *,
    mux_mode: str = "gather",
    noise: NoiseModel | None = None,
    program: dict | None = None,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """One topological sweep: name -> tuple of packed value bit-planes.

    Every entry is a ``value_bits(k)``-tuple of ``(W,)`` (or ``(B, W)``)
    packed words; a binary node's tuple holds its classic single stream.  The
    per-node subkey comes from ``fold_in(key, node index)``, so every node
    draws disjoint counter entropy while parents' planes are shared by all
    their children exactly once -- the correlation structure the joint sample
    requires.  Binary sub-networks draw entropy through exactly the
    pre-categorical code path, keeping their streams bit-identical.

    ``noise`` routes every node through the SAME perturbed integer thresholds
    the fused plan bakes in (:func:`~repro.bayesnet.noise.perturbed_cdf_rows`).
    Binary nodes feed the perturbed threshold back as ``t / 256`` -- exact in
    float32, so the encoder's ``round(p * 256)`` recovers ``t`` bit-for-bit
    and the two lowerings keep sampling the identical perturbed network.
    ``noise=None`` leaves every code path untouched.  ``program`` overrides
    the programmed thresholds fed into the perturbation (calibrate-back
    compensation); with both ``None`` nothing changes.
    """
    order = spec.topo_order()
    perturbed = (
        perturbed_cdf_rows(spec, noise, program=program)
        if noise is not None or program is not None else None
    )
    streams = {}
    for i, name in enumerate(order):
        node = spec.node(name)
        card = spec.card(name)
        pcards = tuple(spec.card(p) for p in node.parents)
        sub = jax.random.fold_in(key, i)
        if not node.parents:
            if card == 2:
                if perturbed is not None:
                    p = jnp.float32(perturbed[name][0][0] / 256.0)
                else:
                    p = jnp.float32(spec.cpt_rows(name)[0][1])
                if batch is not None:
                    p = jnp.full((batch,), p, jnp.float32)
                streams[name] = (rng.encode_packed(sub, p, n_bits),)
            else:
                if perturbed is not None:
                    cdf = perturbed[name][0]
                else:
                    cdf = rng.cdf_thresholds_int(spec.cpt_rows(name)[0])
                planes = rng.encode_packed_categorical(sub, cdf, n_bits, batch=batch)
                streams[name] = tuple(planes[b] for b in range(planes.shape[0]))
        elif card == 2 and all(c == 2 for c in pcards):
            if perturbed is not None:
                cpt = jnp.asarray(
                    tuple(r[0] / 256.0 for r in perturbed[name]), jnp.float32
                )
            else:
                cpt = jnp.asarray(
                    tuple(r[1] for r in spec.cpt_rows(name)), jnp.float32
                )
            if batch is not None:
                cpt = jnp.broadcast_to(cpt, (batch,) + cpt.shape)
            parents = jnp.stack([streams[pn][0] for pn in node.parents])
            streams[name] = (
                node_mux(
                    sub, cpt, parents, n_bits, mode=mux_mode,
                    use_kernel=use_kernel, interpret=interpret,
                ),
            )
        else:
            if perturbed is not None:
                cdf = jnp.asarray(perturbed[name], jnp.uint32)
            else:
                cdf = jnp.asarray(
                    tuple(rng.cdf_thresholds_int(r) for r in spec.cpt_rows(name)),
                    jnp.uint32,
                )
            if batch is not None:
                cdf = jnp.broadcast_to(cdf, (batch,) + cdf.shape)
            parents = jnp.stack(
                [pl for pn in node.parents for pl in streams[pn]]
            )
            planes = node_mux_categorical(
                sub, cdf, parents, cards=(card,) + pcards, n_bits=n_bits,
                use_kernel=use_kernel, interpret=interpret,
            )
            streams[name] = tuple(planes[b] for b in range(planes.shape[0]))
    return streams


def carry_belief(post_state: jnp.ndarray, pred: jnp.ndarray,
                 accepted: jnp.ndarray) -> jnp.ndarray:
    """The row a frame leaves in its track's table entry.

    Its posterior over the state when it accepted a sample; the prediction it
    used when it accepted none (an empty sample has no posterior: the
    belief then only moves by the transition).
    """
    return jnp.where(accepted[:, None] > 0, post_state, pred)


def _tracked_programs(spec: NetworkSpec, queries, plan: SweepPlan,
                      assemble_counts: Callable, sweep_kwargs: dict):
    """The jitted ``run`` / ``decide`` of a tracked network (table donated).

    Each call: gather the frames' table rows, predict (published prior for a
    fresh track, else ``belief @ transition`` in float32), quantise the
    prediction with the 8-bit DAC (:func:`~repro.core.rng.cdf_thresholds`;
    a fresh track takes the plan's own root thresholds, as an untracked
    network does), sweep with the thresholds as extra evidence columns, and
    scatter :func:`carry_belief` back.  Indices past the table read the
    published prior and write nothing (``mode="drop"``).
    """
    k = spec.card(spec.state_node)
    prior = jnp.asarray(spec.cpt_rows(spec.state_node)[0], jnp.float32)
    prior_thr = jnp.asarray(plan.nodes[plan.state][2][0], jnp.int32)
    trans = [[float(p) for p in row] for row in spec.transition]
    off = plan.slot_offsets[queries.index(spec.state_node)]

    def step(key, ev_frames, table, idx, fresh, decide):
        rows = table.at[idx].get(mode="clip")
        # belief @ transition: one float32 product-sum per value, in row order
        cols = []
        for j in range(k):
            acc = rows[:, 0] * jnp.float32(trans[0][j])
            for i in range(1, k):
                acc = acc + rows[:, i] * jnp.float32(trans[i][j])
            cols.append(acc)
        pred = jnp.stack(cols, axis=-1)
        pred = jnp.where(fresh[:, None], prior[None, :], pred)
        thr = jnp.where(fresh[:, None], prior_thr[None, :], rng.cdf_thresholds(pred))
        out = net_sweep(key, jnp.concatenate([ev_frames, thr], axis=1), decide=decide,
                        **sweep_kwargs)
        numer, denom = out[0], out[1]
        slots = numer[:, off : off + k - 1]
        counts = jnp.concatenate(
            [denom[:, None] - jnp.sum(slots, axis=-1, keepdims=True), slots], axis=-1
        )
        belief = cordiv.ratio_from_counts(counts, denom[:, None])
        table = table.at[idx].set(carry_belief(belief, pred, denom), mode="drop")
        return (assemble_counts(numer, denom),) + tuple(out[2:]) + (denom, table)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def _run(key, ev_frames, table, idx, fresh):
        return step(key, ev_frames, table, idx, fresh, False)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def _decide(key, ev_frames, table, idx, fresh):
        return step(key, ev_frames, table, idx, fresh, True)

    return _run, _decide


def _resolve_frame_mesh(devices) -> Tuple[Mesh | None, Tuple[str, ...]]:
    """Mesh + frame-sharding axes for ``compile_network(devices=...)``.

    ``devices=N`` builds the 1-D ``frames`` mesh over the first N local
    devices; ``devices=None`` picks up the ambient
    :func:`~repro.distributed.context.current_mesh` (sharding over its
    :func:`~repro.distributed.sharding.batch_axes`) so launcher code that
    already runs under ``mesh_context`` shards for free.  Returns
    ``(None, ())`` when there is nothing to shard over (one device, no mesh,
    or no batch axis present in the mesh).
    """
    if devices is not None:
        if int(devices) == 1:
            return None, ()
        return dist_context.frame_mesh(int(devices)), ("frames",)
    mesh = dist_context.current_mesh()
    if mesh is None:
        return None, ()
    axes = tuple(
        a for a in dist_sharding.batch_axes(mesh) if a in mesh.axis_names
    )
    if not axes or math.prod(mesh.shape[a] for a in axes) <= 1:
        return None, ()
    return mesh, axes


def compile_network(
    spec: NetworkSpec,
    n_bits: int = 4096,
    queries: Sequence[str] | None = None,
    evidence: Sequence[str] | None = None,
    *,
    share_entropy: bool = False,
    estimator: str = "ratio",
    fused: bool | None = None,
    mux_mode: str = "gather",
    noise: NoiseModel | None = None,
    drift_epochs: int = 1,
    program: dict | None = None,
    devices: int | None = None,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    trace: Tracer | None = None,
) -> CompiledNetwork:
    """Lower ``spec`` to a jitted, frame-batched packed-stochastic program.

    ``fused=None`` auto-selects: the one-launch ``net_sweep`` path whenever it
    applies (independent entropy + ratio estimator -- the production mode),
    the per-node unfused path otherwise.  ``fused=False`` forces the unfused
    program, the statistical verification baseline for the fused kernel.

    ``noise`` (a :class:`~repro.bayesnet.noise.NoiseModel`) injects crossbar
    non-idealities at plan-build time: every 8-bit DAC threshold the program
    samples against is deterministically perturbed (device-to-device lognormal
    spread, cycle-to-cycle read noise, position-dependent IR-drop, stuck-at
    faults) before lowering, in both the fused and unfused paths.
    ``noise=None`` (default) is bit-identical to a compile without the
    argument; the exact perturbed ground truth comes from the oracle twin
    ``make_posterior_fn(spec, noise=...)``.

    ``devices=N`` (fused only) wraps the sweep in one ``shard_map`` launch
    over the frame axis of an N-device mesh; with no ``devices`` argument an
    ambient :func:`~repro.distributed.context.mesh_context` mesh is picked up
    automatically.  Each shard folds its *global* frame origin into the
    entropy counters, so the sharded program is bit-identical to the
    single-device one -- replicating independent samplers is exactly how the
    physical array scales, and costs nothing in reproducibility.  Batches the
    shard count does not divide transparently fall back to the single-device
    launch (the jit is specialised per batch shape anyway).

    ``trace`` (a :class:`~repro.obs.Tracer`) records the lowering as a
    ``compile_network`` span whose attrs carry the plan statistics of
    :func:`network_stats` (nodes, edges, CPT rows, DAC thresholds,
    threshold-mask bytes, value slots).  The span's duration is the
    *lowering* time -- plan construction + jit wrapper building; XLA
    compilation itself is lazy and shows up inside the first launch's
    ``decide.dispatch`` (or ``run.dispatch``) span instead: the returned
    program keeps the tracer and records every call.  ``trace=None``
    changes nothing.
    """
    if trace is not None:
        with trace.span("compile_network", network=spec.name, n_bits=n_bits) as sp:
            net = compile_network(
                spec, n_bits, queries, evidence, share_entropy=share_entropy,
                estimator=estimator, fused=fused, mux_mode=mux_mode,
                noise=noise, drift_epochs=drift_epochs, program=program,
                devices=devices, use_kernel=use_kernel, interpret=interpret,
            )
            sp.attrs.update(network_stats(net))
            return dataclasses.replace(net, trace=trace)
    queries = tuple(queries if queries is not None else spec.queries)
    evidence = tuple(evidence if evidence is not None else spec.evidence)
    if not queries:
        raise ValueError(f"{spec.name}: no query nodes")
    if estimator not in ("ratio", "fill"):
        raise ValueError(f"unknown estimator {estimator!r}")
    if n_bits % 32:
        raise ValueError("n_bits must be a multiple of 32 (packed words)")
    if mux_mode not in ("gather", "rows"):
        raise ValueError(f"unknown mux_mode {mux_mode!r}")
    if mux_mode == "rows" and spec.max_card() > 2:
        raise ValueError(
            "mux_mode='rows' (the binary row-encode baseline) does not "
            "support k-ary nodes; use the default 'gather'"
        )
    if noise is not None and not isinstance(noise, NoiseModel):
        raise TypeError(f"noise must be a NoiseModel or None, got {type(noise)!r}")
    drift_epochs = int(drift_epochs)
    if drift_epochs < 1:
        raise ValueError(f"drift_epochs must be >= 1, got {drift_epochs}")
    if drift_epochs > n_bits // 32:
        raise ValueError(
            f"drift_epochs={drift_epochs} exceeds the {n_bits // 32} packed "
            f"words of n_bits={n_bits} (an epoch owns at least one word)"
        )
    if drift_epochs > 1 and noise is None:
        raise ValueError("drift_epochs > 1 needs a NoiseModel to advance")
    if program is not None:
        unknown = set(program) - set(spec.topo_order())
        if unknown:
            raise ValueError(f"program covers unknown nodes {sorted(unknown)}")
    q_cards = tuple(spec.card(q) for q in queries)
    assemble = _slot_assembler(q_cards)
    # The fused sweep samples with threshold-gather by construction, so a
    # non-default mux_mode is an explicit request for the unfused per-node
    # lowering -- auto-resolution honours it instead of silently ignoring it.
    fusable = not share_entropy and estimator == "ratio" and mux_mode == "gather"
    if fused is None:
        fused = fusable
    elif fused and not fusable:
        raise ValueError(
            "fused lowering requires share_entropy=False, estimator='ratio' "
            f"and mux_mode='gather' (got share_entropy={share_entropy}, "
            f"estimator={estimator!r}, mux_mode={mux_mode!r})"
        )
    if devices is not None and int(devices) > 1 and not fused:
        raise ValueError(
            "devices= sharding requires the fused lowering: per-node unfused "
            "programs draw batch-shaped entropy that is not bit-reproducible "
            "across shard boundaries"
        )
    if drift_epochs > 1 and not fused:
        raise ValueError(
            "drift_epochs > 1 requires the fused lowering: the per-node "
            "unfused encoders sample one threshold snapshot per stream"
        )
    if spec.state is not None:
        if not fused:
            raise ValueError(f"{spec.name}: a tracked network needs the fused lowering")
        if spec.state_node not in queries:
            raise ValueError(
                f"{spec.name}: the state node {spec.state_node!r} must be a query"
            )
    mask = bitops.pad_mask(n_bits)

    if fused:
        plan = sweep_plan(
            spec, queries, evidence, noise=noise,
            drift_epochs=drift_epochs, program=program,
        )
        assemble_counts = _count_assembler(q_cards)
        mesh, shard_axes = _resolve_frame_mesh(devices)
        n_shards = (
            math.prod(mesh.shape[a] for a in shard_axes) if mesh is not None else 1
        )
        sweep_kwargs = dict(
            plan=plan, n_bits=n_bits, use_kernel=use_kernel, interpret=interpret
        )

        def launch(key, ev_frames, decide: bool):
            """One sweep launch: sharded over the frame axis when it divides.

            The per-shard body folds the shard's global frame origin into
            ``net_sweep``'s entropy counters (``frame0`` / ``total_frames``),
            which makes the sharded launch bit-identical to the single-device
            one -- asserted for every scenario in the sharding tests.
            """
            b = ev_frames.shape[0]
            if mesh is None or n_shards <= 1 or b % n_shards:
                return net_sweep(key, ev_frames, decide=decide, **sweep_kwargs)
            per_shard = b // n_shards
            ax = shard_axes if len(shard_axes) > 1 else shard_axes[0]
            bspec = P(ax)

            def body(kd, ev_local):
                idx = jnp.uint32(0)
                for a in shard_axes:
                    idx = idx * jnp.uint32(mesh.shape[a]) \
                        + jax.lax.axis_index(a).astype(jnp.uint32)
                return net_sweep(
                    kd, ev_local, frame0=idx * jnp.uint32(per_shard),
                    total_frames=b, decide=decide, **sweep_kwargs,
                )

            return jax.shard_map(
                body, mesh=mesh, in_specs=(P(), bspec),
                out_specs=(bspec,) * (3 if decide else 2), check_vma=False,
            )(rng.seed_words(key), ev_frames)

        if spec.state is not None:
            if n_shards > 1:
                raise ValueError(
                    f"{spec.name}: a tracked network runs on one device (its "
                    "belief table is not sharded)"
                )
            _run, _decide = _tracked_programs(spec, queries, plan, assemble_counts,
                                              sweep_kwargs)
            return CompiledNetwork(
                spec=spec, queries=queries, evidence=evidence, n_bits=n_bits,
                share_entropy=share_entropy, estimator=estimator, fused=True,
                query_cards=q_cards, _run=_run, _decide=_decide,
                noise=noise, drift_epochs=drift_epochs, program=program,
            )

        @jax.jit
        def _run(key, ev_frames):
            numer, denom = launch(key, ev_frames, False)
            return assemble_counts(numer, denom), denom

        @jax.jit
        def _decide(key, ev_frames):
            numer, denom, dec = launch(key, ev_frames, True)
            return assemble_counts(numer, denom), dec, denom

        return CompiledNetwork(
            spec=spec, queries=queries, evidence=evidence, n_bits=n_bits,
            share_entropy=share_entropy, estimator=estimator, fused=True,
            query_cards=q_cards, _run=_run, _decide=_decide,
            n_shards=n_shards, shard_axes=shard_axes if mesh is not None else (),
            noise=noise, drift_epochs=drift_epochs, program=program,
        )

    def slot_indicators(streams):
        """Per-query per-value (1..k-1) indicator streams, slot order."""
        slots = []
        for q, c in zip(queries, q_cards):
            pls = streams[q]
            if c == 2:
                slots.append(pls[0])
            else:
                for v in range(1, c):
                    slots.append(bitops.digit_indicator(pls, v))
        return tuple(slots)

    def one_frame(ev, ev_planes, slot_streams):
        """ev (n_ev,); ev_planes: per-evidence plane tuples; slots (n_s, W)."""
        denom = jnp.broadcast_to(mask, mask.shape)
        for i in range(len(evidence)):
            for b, s in enumerate(ev_planes[i]):
                # value indicator, plane literal at a time (binary: the node
                # stream for e=1, its packed NOT for e=0)
                term = s ^ jnp.where(((ev[i] >> b) & 1) == 1, jnp.uint32(0), mask)
                denom = denom & term
        numer = jnp.stack(slot_streams) & denom[None, :]
        _, post = cordiv.cordiv_fill(numer, denom[None, :], n_bits)
        return post, bitops.popcount(denom)

    def ratio_batched(ev_frames, ev_planes, slot_streams):
        """Straight-line batched conditioning for the ratio estimator.

        Computes the popcounts of the acceptance stream ``one_frame`` builds
        and of each slot indicator ANDed with it, with indicators broadcast
        across the frame axis instead of per-frame ``vmap`` closures.  Plane
        arrays are (W,) shared or (B, W) independent.  Returns raw counts
        ``(numer (B, n_s), denom (B,))`` so the caller can assemble the
        posterior count-exactly.
        """
        b = ev_frames.shape[0]
        accept = jnp.broadcast_to(mask, (b, mask.shape[0]))
        for i in range(len(evidence)):
            for bit, s in enumerate(ev_planes[i]):
                s = s if s.ndim == 2 else s[None, :]
                ebit = (ev_frames[:, i : i + 1] >> bit) & 1
                ind = s ^ jnp.where(ebit == 1, jnp.uint32(0), mask[None, :])
                accept = accept & ind
        denom = bitops.popcount(accept)
        numer = jnp.stack(
            [
                bitops.popcount(accept & (s if s.ndim == 2 else s[None, :]))
                for s in slot_streams
            ],
            axis=-1,
        )
        return numer, denom

    assemble_counts = _count_assembler(q_cards)

    @jax.jit
    def _run(key, ev_frames):
        b = ev_frames.shape[0]
        streams = lower_streams(
            spec, key, n_bits, batch=None if share_entropy else b,
            mux_mode=mux_mode, noise=noise, program=program,
            use_kernel=use_kernel, interpret=interpret,
        )
        ev_planes = tuple(streams[e] for e in evidence)
        slots = slot_indicators(streams)
        if estimator == "ratio":
            # count-exact assembly, like the fused path: equal counts give
            # equal floats, so posterior_argmax ties break on the lowest
            # value here too (the fill path has no counts to assemble from)
            numer, denom = ratio_batched(ev_frames, ev_planes, slots)
            return assemble_counts(numer, denom), denom
        if share_entropy:
            post, denom = jax.vmap(one_frame, in_axes=(0, None, None))(
                ev_frames, ev_planes, slots
            )
        else:
            # independent entropy: every plane carries a leading frame axis
            post, denom = jax.vmap(one_frame)(ev_frames, ev_planes, slots)
        return assemble(post), denom

    @jax.jit
    def _decide(key, ev_frames):
        post, denom = _run(key, ev_frames)
        return post, posterior_argmax(post), denom

    return CompiledNetwork(
        spec=spec, queries=queries, evidence=evidence, n_bits=n_bits,
        share_entropy=share_entropy, estimator=estimator, fused=False,
        query_cards=q_cards, _run=_run, _decide=_decide, noise=noise,
        program=program,
    )
