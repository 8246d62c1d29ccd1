"""Plain reference: exact posteriors of a network by full enumeration.

Reads a network file of ``chipbench/networks/`` and nothing of the program.
The system samples every CPT row through an 8-bit DAC: the tail sums
``P(value >= v)`` are rounded half-to-even onto the ``t / 256`` grid (kept
non-increasing) and one entropy byte picks the value.  So the distribution the
system samples, and the one enumerated here, has the per-value probabilities
``(C_v - C_{v+1}) / 256`` with ``C_0 = 256`` and ``C_k = 0``.

Everything is float64 numpy over the ``prod(cards)`` joint states; each
network's exact answer is a table over its evidence patterns, so a frame is
checked by a lookup.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

NETWORKS = Path(__file__).resolve().parent / "networks"


def dac_thresholds(probs) -> tuple:
    """Cumulative 8-bit DAC thresholds ``C_1 .. C_{k-1}`` of one CPT row."""
    out, prev = [], 256
    for v in range(1, len(probs)):
        tail = float(np.sum(np.asarray(probs[v:], np.float64)))
        t = int(np.clip(np.round(np.float32(tail) * 256.0), 0.0, 256.0))
        t = min(t, prev)
        out.append(t)
        prev = t
    return tuple(out)


def dac_row(probs) -> np.ndarray:
    """The per-value probabilities the DAC actually samples for one row."""
    bounds = (256,) + dac_thresholds(probs) + (0,)
    return np.asarray(
        [(bounds[v] - bounds[v + 1]) / 256.0 for v in range(len(probs))], np.float64
    )


class Network:
    """One network file: nodes, CPTs, evidence and queries, enumerated exactly."""

    def __init__(self, doc: dict):
        self.name = doc["name"]
        self.nodes = [n["name"] for n in doc["nodes"]]
        self.cards = [int(n["k"]) for n in doc["nodes"]]
        self.parents = [list(n["parents"]) for n in doc["nodes"]]
        self.rows = [np.stack([dac_row(r) for r in n["rows"]]) for n in doc["nodes"]]
        self.evidence = list(doc["evidence"])
        self.queries = list(doc["queries"])
        idx = {n: j for j, n in enumerate(self.nodes)}
        self.ev_cols = [idx[e] for e in self.evidence]
        self.q_cols = [idx[q] for q in self.queries]
        self.ev_cards = [self.cards[j] for j in self.ev_cols]
        self.q_cards = [self.cards[j] for j in self.q_cols]
        self.binary_layout = all(c == 2 for c in self.q_cards)
        self.kmax = max(self.q_cards)
        self._exact()

    @classmethod
    def load(cls, name: str, directory: Path = NETWORKS) -> "Network":
        path = Path(directory) / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no network file {path}")
        with open(path) as f:
            return cls(json.load(f))

    def _exact(self) -> None:
        """Joint over all states, then per evidence pattern: P(e) and posteriors."""
        total = math.prod(self.cards)
        s = np.arange(total)
        states = []
        for c in self.cards:
            states.append(s % c)
            s = s // c
        states = np.stack(states, axis=-1)                      # (S, N)
        idx = {n: j for j, n in enumerate(self.nodes)}
        joint = np.ones(total, np.float64)
        for j, _ in enumerate(self.nodes):
            row = np.zeros(total, np.int64)
            for p in self.parents[j]:                           # first parent most significant
                row = row * self.cards[idx[p]] + states[:, idx[p]]
            joint *= self.rows[j][row, states[:, j]]
        pattern = self.pattern_index(states[:, self.ev_cols])   # (S,)
        n_pat = math.prod(self.ev_cards)
        self.p_e = np.bincount(pattern, weights=joint, minlength=n_pat)
        post = np.zeros((n_pat, len(self.queries), self.kmax), np.float64)
        for qi, col in enumerate(self.q_cols):
            for v in range(self.q_cards[qi]):
                mass = np.bincount(
                    pattern, weights=joint * (states[:, col] == v), minlength=n_pat
                )
                post[:, qi, v] = np.divide(
                    mass, self.p_e, out=np.zeros_like(mass), where=self.p_e > 0
                )
        self.post = post                                        # (P, n_q, kmax)

    def pattern_index(self, ev) -> np.ndarray:
        """Mixed-radix index of evidence rows (first evidence column fastest)."""
        ev = np.asarray(ev, np.int64)
        out = np.zeros(ev.shape[0], np.int64)
        for j in reversed(range(len(self.ev_cards))):
            out = out * self.ev_cards[j] + ev[:, j]
        return out

    def pattern_rows(self, patterns) -> np.ndarray:
        """Evidence rows (n, n_ev) int32 of pattern indices."""
        p = np.asarray(patterns, np.int64)
        cols = []
        for c in self.ev_cards:
            cols.append(p % c)
            p = p // c
        return np.stack(cols, axis=-1).astype(np.int32)

    def sample_rows(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """``n`` evidence rows drawn as the network predicts its sensors fire."""
        return self.pattern_rows(rng.choice(self.p_e.size, size=n, p=self.p_e / self.p_e.sum()))

    def argmax(self, post) -> np.ndarray:
        """MAP decision of a posterior in the system's layout (ties to the lowest value)."""
        post = np.asarray(post)
        if post.ndim == 2:
            return (post > 0.5).astype(np.int32)
        return np.argmax(post, axis=-1).astype(np.int32)

    def count_argmax(self, post, accepted) -> np.ndarray:
        """MAP decision of each frame's sample from its per-value counts.

        The counts are ``rint(posterior x accepted)`` (value 0 of a binary
        query: ``accepted - n_1``); ties go to the lowest value.  Exact for
        any posterior within half a count of ``count / accepted``.
        """
        post = np.asarray(post, np.float64)
        acc = np.asarray(accepted, np.float64).reshape(-1)
        if post.ndim == 2:
            n1 = np.rint(post * acc[:, None])
            return (n1 > acc[:, None] - n1).astype(np.int32)
        counts = np.rint(post * acc[:, None, None])
        return np.argmax(counts, axis=-1).astype(np.int32)

    def count_tied(self, post, accepted) -> np.ndarray:
        """Where two values of a query share the frame's largest count, (frames, n_q) bool."""
        post = np.asarray(post, np.float64)
        acc = np.asarray(accepted, np.float64).reshape(-1)
        if post.ndim == 2:
            n1 = np.rint(post * acc[:, None])
            return (2 * n1 == acc[:, None]) & (acc[:, None] > 0)
        counts = np.rint(post * acc[:, None, None])
        top = counts.max(axis=-1, keepdims=True)
        return (np.sum(counts == top, axis=-1) > 1) & (acc[:, None] > 0)

    def entries(self, post):
        """(compared posterior values, value index per entry) in a common layout.

        The system returns ``(B, n_q)`` of ``P(q = 1)`` when every query is
        binary, else ``(B, n_q, kmax)`` per-value vectors.  Compared entries:
        ``P(q = 1)`` of a binary query and every value of a k-ary one.
        """
        post = np.asarray(post, np.float64)
        got, val = [], []
        for qi, c in enumerate(self.q_cards):
            values = (1,) if c == 2 else tuple(range(c))
            for v in values:
                got.append(post[:, qi] if post.ndim == 2 else post[:, qi, v])
                val.append((qi, v))
        return np.stack(got, axis=-1), val
