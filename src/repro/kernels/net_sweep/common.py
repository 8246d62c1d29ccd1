"""Shared bit-sliced sweep body for the fused whole-network op.

``SweepPlan`` is the static (hashable) description of one compiled network:
per-node parent indices, cardinality, and per-row 8-bit DAC **CDF thresholds**
in topological order, plus the evidence/query node sets.  ``sweep_tile`` runs
the full topological sweep for one ``(frames x words)`` tile and returns the
popcount partials -- it is the single source of truth for the fused semantics,
called on the whole array by the jnp reference and per-tile by the Pallas
kernel, which makes the two bit-identical by construction (the kernel tests
then pin the tiling and accumulation).

Node sampling is the categorical threshold-gather formulation in bit-sliced
form: entropy arrives as 8 *bit-planes* per output word (``rng.plane_base`` /
``rng.plane_word``) -- ONE byte per stream position regardless of cardinality.
A cardinality-``k`` node carries ``k-1`` non-increasing cumulative thresholds
per CPT row (``C_v`` encodes ``P(value >= v)``); each threshold's gathered
per-plane mask words (an OR of parent-digit indicator words for every CPT row
whose threshold has that bit set -- constant-folded at trace time) feed the
borrow-chain comparator, the ``k-1`` chains share the node's 8 entropy planes,
and the sampled value ``#{v : byte < C_v}`` is re-packed as ``value_bits(k)``
bit-planes.  Planes below the lowest set threshold bit of a node can never
flip any comparison and are skipped entirely.  Binary nodes (``k=2``) collapse
to exactly the single-chain lowering -- one threshold, one plane, bit-identical
streams.

A plan with a tracked ``state`` root takes that root's ``k-1`` thresholds per
frame instead of from its CPT row: they ride in as ``k-1`` extra int32
columns of the evidence tile, after the evidence columns, and feed the same
borrow chain as per-frame mask words (every plane is generated, since no
bit is known clear at trace time).  Plans without a state lower exactly as
before.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops, rng
from repro.kernels import backend

# np scalar (not a committed jax array): Pallas kernels cannot close over
# device constants, and np scalars fold into jaxpr literals.
_FULL = np.uint32(0xFFFFFFFF)

# Trace-time sentinel: a threshold-bit mask that is all-ones across the tile
# (every CPT row has this bit set) -- lets the borrow chain drop the AND.
_ONES = object()


def _normalize_node(entry):
    """Accept the legacy ``(parents, scalar thresholds)`` node form.

    Pre-categorical plans carried one 8-bit threshold per CPT row (binary
    nodes only); they normalise to cardinality 2 with one-level CDF rows, so
    existing plan constructions keep working unchanged.
    """
    if len(entry) == 2:
        parents, thresh = entry
        return (tuple(parents), 2, tuple((int(t),) for t in thresh))
    parents, card, rows = entry
    return (tuple(parents), int(card), tuple(tuple(int(t) for t in r) for r in rows))


def _check_rows(i: int, card: int, n_expect: int, rows) -> None:
    """Shared CDF-row validation for base and epoch rows of one node."""
    if len(rows) != n_expect:
        raise ValueError(f"node {i}: needs {n_expect} CPT rows, got {len(rows)}")
    for row in rows:
        if len(row) != card - 1:
            raise ValueError(f"node {i}: CDF row {row} needs {card - 1} thresholds")
        prev = 256
        for t in row:
            if not 0 <= t <= 256:
                raise ValueError(f"node {i}: threshold {t} outside [0, 256]")
            if t > prev:
                raise ValueError(f"node {i}: CDF thresholds {row} not non-increasing")
            prev = t


def epoch_word_bounds(w_words: int, epochs: int) -> Tuple[int, ...]:
    """Word-index partition of a launch's bit-stream into drift epochs.

    ``epochs + 1`` non-decreasing bounds: epoch ``e`` owns words
    ``[bounds[e], bounds[e+1])``.  Maximally even split, earlier epochs take
    the remainder -- a pure function of ``(w_words, epochs)`` shared by the
    sweep lowering and the analytic oracle's mixture weights so both sides
    weight each epoch by exactly the bits it emits.
    """
    if epochs < 1:
        raise ValueError(f"epochs must be >= 1, got {epochs}")
    return tuple(round(e * w_words / epochs) for e in range(epochs + 1))


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """Static lowering of a k-ary DAG network for the fused sweep.

    nodes:    per node (in topological order) a triple ``(parents, card,
              rows)``: ``parents`` are indices of earlier nodes (first parent
              = most significant mixed-radix CPT row digit), ``card`` is the
              node's cardinality, and ``rows`` holds one ``(card - 1,)`` tuple
              of non-increasing cumulative 8-bit DAC thresholds in [0, 256]
              per parent assignment (``rng.cdf_thresholds_int``).  The legacy
              binary pair form ``(parents, thresholds)`` is normalised on
              construction.
    evidence: node index per evidence frame column (values in ``[0, card)``).
    queries:  node index per posterior output; each query of cardinality k
              contributes ``k - 1`` numerator slots (values ``1 .. k-1``; the
              value-0 count is ``denom`` minus their sum).
    epochs:   within-launch drift epochs.  The word axis is split by
              :func:`epoch_word_bounds`; words of epoch ``e > 0`` compare
              against ``epoch_rows[e - 1]`` instead of the base rows --
              modelling the crossbar's read-noise snapshot advancing *during*
              one launch.  Entropy is untouched (the counter layout never
              sees epochs), so ``epochs=1`` is bit-identical to the
              pre-drift plan by construction.
    epoch_rows: ``epochs - 1`` entries, each a per-node tuple of threshold
              row tuples with the same shape as that node's base ``rows``
              (same parents, same cardinality -- only the programmed
              thresholds drift).
    state:    index of a root whose ``card - 1`` thresholds arrive per frame
              as the evidence tile's last ``n_state_cols`` columns (every
              epoch uses them); ``None`` for a plan without tracked state.
    """

    nodes: Tuple
    evidence: Tuple[int, ...]
    queries: Tuple[int, ...]
    epochs: int = 1
    epoch_rows: Tuple = ()
    state: int | None = None

    def __post_init__(self):
        object.__setattr__(
            self, "nodes", tuple(_normalize_node(e) for e in self.nodes)
        )
        object.__setattr__(self, "evidence", tuple(self.evidence))
        object.__setattr__(self, "queries", tuple(self.queries))
        object.__setattr__(self, "epochs", int(self.epochs))
        object.__setattr__(
            self,
            "epoch_rows",
            tuple(
                tuple(tuple(tuple(int(t) for t in row) for row in node_rows)
                      for node_rows in per_epoch)
                for per_epoch in self.epoch_rows
            ),
        )
        for i, (parents, card, rows) in enumerate(self.nodes):
            if card < 2:
                raise ValueError(f"node {i}: cardinality {card} < 2")
            for p in parents:
                if not 0 <= p < i:
                    raise ValueError(f"node {i}: parent {p} not earlier in topo order")
            expect = math.prod(self.nodes[p][1] for p in parents)
            if len(rows) != expect:
                raise ValueError(
                    f"node {i}: {len(parents)} parents of cardinalities "
                    f"{tuple(self.nodes[p][1] for p in parents)} need {expect} "
                    f"CPT rows, got {len(rows)}"
                )
            _check_rows(i, card, expect, rows)
        for n in self.evidence + self.queries:
            if not 0 <= n < len(self.nodes):
                raise ValueError(f"evidence/query node {n} out of range")
        if not self.queries:
            raise ValueError("SweepPlan needs at least one query node")
        if self.state is not None:
            object.__setattr__(self, "state", int(self.state))
            if not 0 <= self.state < len(self.nodes) or self.nodes[self.state][0]:
                raise ValueError(f"state node {self.state} is not a root of the plan")
        if self.epochs < 1:
            raise ValueError(f"epochs must be >= 1, got {self.epochs}")
        if len(self.epoch_rows) != self.epochs - 1:
            raise ValueError(
                f"epochs={self.epochs} needs {self.epochs - 1} epoch_rows "
                f"entries, got {len(self.epoch_rows)}"
            )
        for e, per_epoch in enumerate(self.epoch_rows):
            if len(per_epoch) != len(self.nodes):
                raise ValueError(
                    f"epoch {e + 1}: rows for {len(per_epoch)} nodes, "
                    f"plan has {len(self.nodes)}"
                )
            for i, node_rows in enumerate(per_epoch):
                _check_rows(i, self.nodes[i][1], len(self.nodes[i][2]), node_rows)

    # ------------------------------------------------------------- accessors
    def card(self, i: int) -> int:
        return self.nodes[i][1]

    @property
    def n_value_slots(self) -> int:
        """Numerator count columns: ``sum(card - 1)`` over the query nodes."""
        return sum(self.nodes[q][1] - 1 for q in self.queries)

    @property
    def n_state_cols(self) -> int:
        """Per-frame threshold columns after the evidence (0 without a state)."""
        return 0 if self.state is None else self.nodes[self.state][1] - 1

    @property
    def query_cards(self) -> Tuple[int, ...]:
        """Cardinality per query node, in query order."""
        return tuple(self.nodes[q][1] for q in self.queries)

    @property
    def slot_offsets(self) -> Tuple[int, ...]:
        """First numerator slot column of each query (queries own contiguous
        runs of ``card - 1`` slots, in plan order)."""
        offs, off = [], 0
        for q in self.queries:
            offs.append(off)
            off += self.nodes[q][1] - 1
        return tuple(offs)

    def node_rows(self, n: int, epoch: int = 0) -> Tuple:
        """CDF rows of node ``n`` in drift epoch ``epoch`` (0 = base rows)."""
        return self.nodes[n][2] if epoch == 0 else self.epoch_rows[epoch - 1][n]


class _RowSetGather:
    """Trace-time-factored OR of CPT-row indicators for one node.

    A threshold-bit mask is the indicator of a *set* of CPT rows.  Building it
    as a flat OR of per-row AND-of-literals words costs ``O(L * m)`` ops per
    mask; factoring the set parent-by-parent (a digit ``d`` whose whole
    sub-space is selected contributes just the digit indicator) and memoising
    the recursive sub-sets -- which repeat heavily across the ``8 * (card-1)``
    masks of a k-ary node -- cuts the gate count severalfold.  Pure boolean
    restructuring: the produced words are value-identical to the flat OR, so
    binary plans stay bit-identical.
    """

    def __init__(self, streams, parents, pcards):
        self.pcards = pcards
        self.sizes = [math.prod(pcards[j:]) for j in range(len(pcards))] + [1]
        self._digits = {}
        self._sets = {}
        self._streams = streams
        self._parents = parents

    def digit(self, j, d):
        if (j, d) not in self._digits:
            self._digits[(j, d)] = bitops.digit_indicator(
                self._streams[self._parents[j]], d
            )
        return self._digits[(j, d)]

    def rows(self, selected):
        """``selected``: iterable of mixed-radix row indices -> mask word,
        ``None`` (empty) or ``_ONES`` (the full parent space)."""
        return self._gather(0, frozenset(selected))

    def _gather(self, j, sel):
        if not sel:
            return None
        if len(sel) == self.sizes[j]:
            return _ONES
        memo_key = (j, sel)
        if memo_key in self._sets:
            return self._sets[memo_key]
        sub_size = self.sizes[j + 1]
        acc = None
        for d in range(self.pcards[j]):
            sub = frozenset(r - d * sub_size for r in sel
                            if d * sub_size <= r < (d + 1) * sub_size)
            inner = self._gather(j + 1, sub)
            if inner is None:
                continue
            term = self.digit(j, d) if inner is _ONES else self.digit(j, d) & inner
            acc = term if acc is None else acc | term
        self._sets[memo_key] = acc
        return acc


def _lt_chain(plane, thresh_masks, hi, shape):
    """Bit-sliced ``byte < threshold`` borrow chain over the needed planes.

    ``plane(k)`` returns entropy bit-plane ``k`` (memoised by the caller, so
    the k-1 chains of one categorical node share the node's 8 planes).
    thresh_masks[k] is the packed mask of threshold bit ``k`` per position
    (None = bit clear everywhere, ``_ONES`` = set everywhere); ``hi`` marks
    positions whose threshold is 256 (always fires).  Planes below the lowest
    set threshold bit cannot flip a strict less-than against a zero tail and
    are never generated.
    """
    lo = 8
    for k in range(8):
        if thresh_masks[k] is not None:
            lo = k
            break
    lt = None
    eq = None
    for k in range(7, lo - 1, -1):
        r = plane(k)
        t = thresh_masks[k]
        if t is None:
            eq = ~r if eq is None else eq & ~r
        elif t is _ONES:
            c = ~r if eq is None else eq & ~r
            lt = c if lt is None else lt | c
            eq = r if eq is None else eq & r
        else:
            c = (~r & t) if eq is None else (eq & ~r & t)
            lt = c if lt is None else lt | c
            eq = ~(r ^ t) if eq is None else eq & ~(r ^ t)
    if lt is None:
        lt = jnp.zeros(shape, jnp.uint32)
    if hi is not None:
        lt = lt | (jnp.broadcast_to(_FULL, shape) if hi is _ONES else hi)
    return lt


def _level_masks(rows, level, gather, l):
    """Per-plane gathered mask words + the t=256 short-circuit for one level."""
    if gather is None:  # root: one static row
        t = rows[0][level]
        masks = [(_ONES if (t >> k) & 1 else None) for k in range(8)]
        hi = _ONES if t >= 256 else None
        return masks, hi
    masks = [
        gather.rows([r for r in range(l) if (rows[r][level] >> k) & 1])
        for k in range(8)
    ]
    hi = gather.rows([r for r in range(l) if rows[r][level] >= 256])
    return masks, hi


def _frame_level_masks(thresh):
    """Per-plane mask words of per-frame thresholds: thresh (bf, 1) int32.

    The tracked root's counterpart of :func:`_level_masks`: bit ``k`` of each
    frame's threshold, spread over the frame's words, and the ``t = 256``
    short-circuit.  No plane is known clear at trace time, so the borrow
    chain generates all eight.
    """
    masks = [jnp.where(((thresh >> k) & 1) == 1, _FULL, jnp.uint32(0)) for k in range(8)]
    hi = jnp.where(thresh >= 256, _FULL, jnp.uint32(0))
    return masks, hi


def _combine_epochs(per_epoch, emasks):
    """OR of per-epoch threshold-bit masks restricted to their word ranges.

    ``per_epoch[e]`` is one epoch's mask (None / ``_ONES`` / word) and
    ``emasks[e]`` the full-ones-where-epoch-``e`` word for the tile.  The
    emasks partition every tile position, so all-None stays None and
    all-``_ONES`` stays ``_ONES`` -- the static short-circuits (and with them
    the skipped-plane optimisation) survive epoching whenever the epochs
    agree on a bit.
    """
    if all(m is None for m in per_epoch):
        return None
    if all(m is _ONES for m in per_epoch):
        return _ONES
    acc = None
    for em, m in zip(emasks, per_epoch):
        if m is None:
            continue
        term = em if m is _ONES else em & m
        acc = term if acc is None else acc | term
    return acc


def _epoch_level_masks(plan, n, level, gather, l, emasks):
    """Epoch-aware :func:`_level_masks`: per-epoch rows folded under emasks.

    One ``_RowSetGather`` serves every epoch of the node (digit indicators
    and recursive row-set words are epoch-independent, so the memo is shared);
    only the selected row sets differ per epoch.
    """
    per_bits = []
    per_hi = []
    for e in range(plan.epochs):
        masks, hi = _level_masks(plan.node_rows(n, e), level, gather, l)
        per_bits.append(masks)
        per_hi.append(hi)
    masks = [
        _combine_epochs([per_bits[e][k] for e in range(plan.epochs)], emasks)
        for k in range(8)
    ]
    hi = _combine_epochs(per_hi, emasks)
    return masks, hi


def decide_counts(plan: SweepPlan, numer: jnp.ndarray, denom: jnp.ndarray):
    """Decision epilogue: per-query argmax value from the count slots.

    ``numer`` holds the per-query-value acceptance popcounts (values
    ``1 .. card-1`` per query); the value-0 count is ``denom`` minus the
    query's slots.  The argmax over the full count vector IS the argmax of
    the per-value posterior (same positive denominator, same tie-break:
    lowest value wins), so the fused decision is bit-identical to
    posterior-argmax by construction.  A frame that accepted no stream
    positions (``denom == 0``) decides value 0, matching the all-zero
    posterior convention of :func:`~repro.core.cordiv.ratio_from_counts`.

    numer (..., n_value_slots) i32, denom (...,) i32 -> (..., n_q) i32.
    """
    decs = []
    for q_card, off in zip(plan.query_cards, plan.slot_offsets):
        slots = numer[..., off : off + q_card - 1]
        c0 = denom - jnp.sum(slots, axis=-1)
        counts = jnp.concatenate([c0[..., None], slots], axis=-1)
        decs.append(backend.first_argmax(counts))
    return jnp.stack(decs, axis=-1)


def sweep_tile(
    plan: SweepPlan,
    kd0,
    kd1,
    ev: jnp.ndarray,
    f0,
    w0,
    bf: int,
    bw: int,
    w_words: int,
    n_frames: int,
    decide: bool = False,
):
    """Counts for one tile: frames ``[f0, f0+bf)`` x words ``[w0, w0+bw)``.

    ev: (bf, >= n_ev) int32 evidence values for the tile's frames (one integer
    in ``[0, card)`` per evidence node), then, for a plan with a ``state``,
    the state root's ``card - 1`` thresholds per frame.  Returns ``(numer (bf, n_value_slots)
    int32, denom (bf,) int32)`` -- popcounts of the acceptance stream and of
    each query value indicator ANDed with it, over this tile's words only
    (callers accumulate across word tiles).  Slot order: queries in plan
    order, values ``1 .. card-1`` within a query.

    The entropy counter for node ``n``, frame ``f``, word ``w`` is
    ``n * n_frames * w_words + f * w_words + w`` -- one base counter per
    output word, planes salted from it, ONE byte per stream position no
    matter the cardinality -- so tiles of any shape draw identical bits for
    identical global positions, and binary plans consume exactly the
    pre-categorical entropy layout.  ``f0`` may be a traced uint32 scalar:
    a shard of a larger launch passes its *global* frame origin (and the
    global ``n_frames``), which is all it takes for sharded output to be
    bit-identical to the single-device sweep.

    ``decide=True`` appends the :func:`decide_counts` epilogue -- per-query
    argmax straight off the in-register popcounts -- and returns
    ``(numer, denom, decisions (bf, n_q) i32)``.  Only valid when the tile
    spans the full word axis (partial-word counts cannot be argmaxed).
    """
    if decide and bw != w_words:
        raise ValueError(
            f"decide epilogue needs the full word axis in one tile "
            f"(bw={bw}, w_words={w_words}); argmax over partial counts is wrong"
        )
    fi = jax.lax.broadcasted_iota(jnp.uint32, (bf, bw), 0)
    wi = jax.lax.broadcasted_iota(jnp.uint32, (bf, bw), 1)
    pos = (jnp.asarray(f0, jnp.uint32) + fi) * jnp.uint32(w_words) \
        + jnp.asarray(w0, jnp.uint32) + wi
    emasks = None
    if plan.epochs > 1:
        # Epoch membership is a pure function of the *global* word index, so
        # any tiling (and any shard) assigns identical epochs to identical
        # positions.  Entropy is untouched: only the threshold masks switch.
        wglob = jnp.asarray(w0, jnp.uint32) + wi
        bounds = epoch_word_bounds(w_words, plan.epochs)
        emasks = [
            jnp.where(
                (wglob >= jnp.uint32(lo)) & (wglob < jnp.uint32(hi)),
                _FULL, jnp.uint32(0),
            )
            for lo, hi in zip(bounds, bounds[1:])
        ]
    streams = []        # per node: tuple of value bit-plane words
    node_buckets = []   # per node: tuple of value==v indicator words, v=1..k-1
    for n, (parents, card, rows) in enumerate(plan.nodes):
        node_off = jnp.uint32((n * n_frames * w_words) & 0xFFFFFFFF)
        base = rng.plane_base(node_off + pos, kd0)
        l = len(rows)
        if not parents:
            gather = None
        else:
            # Threshold-bit masks are factored ORs of CPT-row indicators over
            # the parents' digit indicators, first parent = most significant
            # mixed-radix digit (the spec.py / Fig S8 ordering), memoised
            # across the node's masks (see _RowSetGather).
            pcards = tuple(plan.card(p) for p in parents)
            gather = _RowSetGather(streams, parents, pcards)
        plane_cache = {}

        def plane(k, base=base):
            if k not in plane_cache:
                plane_cache[k] = rng.plane_word(base, kd1, k)
            return plane_cache[k]

        levels = []
        for v in range(card - 1):
            if n == plan.state:
                col = len(plan.evidence) + v
                masks, hi = _frame_level_masks(ev[:, col : col + 1])
            elif emasks is None:
                masks, hi = _level_masks(rows, v, gather, l)
            else:
                masks, hi = _epoch_level_masks(plan, n, v, gather, l, emasks)
            levels.append(_lt_chain(plane, masks, hi, (bf, bw)))
        bks = bitops.nested_buckets(levels)
        streams.append(tuple(bitops.planes_from_buckets(bks)))
        node_buckets.append(tuple(bks))
    accept = None
    for col, e in enumerate(plan.evidence):
        ind = None
        for b, pl in enumerate(streams[e]):
            bit = (ev[:, col : col + 1] >> b) & 1
            term = pl ^ jnp.where(bit == 1, jnp.uint32(0), _FULL)
            ind = term if ind is None else ind & term
        accept = ind if accept is None else accept & ind
    if accept is None:
        accept = jnp.broadcast_to(_FULL, (bf, bw))
    denom = jnp.sum(jax.lax.population_count(accept).astype(jnp.int32), axis=-1)
    numer = jnp.stack(
        [
            jnp.sum(
                jax.lax.population_count(accept & bk).astype(jnp.int32), axis=-1
            )
            for q in plan.queries
            for bk in node_buckets[q]
        ],
        axis=-1,
    )
    if decide:
        return numer, denom, decide_counts(plan, numer, denom)
    return numer, denom
