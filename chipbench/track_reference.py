"""Plain reference of a tracked network: the prior a tracker predicts, and the network under it.

Reads a network file of ``chipbench/networks/`` and the configuration's
transition, and imports nothing of the program.  A tracked root's prior at a
frame is the published prior for a fresh track, else the track's previous
answered belief times the transition (``stay`` on the diagonal, the rest
split evenly), quantised with the 8-bit DAC of every CPT row
(``reference.dac_thresholds``).  The network under that prior is the
network file with the root's row replaced by the DAC row of those
thresholds, enumerated exactly by :class:`chipbench.reference.Network`.  A
frame that accepted no sample leaves the prediction itself as its track's
belief.
"""

from __future__ import annotations

import copy
from typing import Dict, Tuple

import numpy as np

from chipbench.reference import Network, dac_thresholds

EDGE = 1e-3   # a tail x 256 this close to a rounding boundary may round either way in float32


def transition(k: int, stay: float) -> np.ndarray:
    """``k x k`` rows: stay with probability ``stay``, the rest split evenly."""
    t = np.full((k, k), (1.0 - stay) / (k - 1))
    np.fill_diagonal(t, stay)
    return t


class Tracked:
    """One network file with a tracked root: predictions and the networks under them."""

    def __init__(self, doc: dict, root: str, stay: float):
        self.doc = doc
        self.name = doc["name"]
        self.root = root
        node = next((n for n in doc["nodes"] if n["name"] == root), None)
        if node is None or node["parents"] or root not in doc["queries"]:
            raise ValueError(f"{self.name}: {root!r} is not a root query of the network")
        self.k = int(node["k"])
        self.stay = float(stay)
        self.trans = transition(self.k, self.stay)
        self.published = np.asarray(node["rows"][0], np.float64)
        self.q = doc["queries"].index(root)
        self._nets: Dict[Tuple[int, ...], Network] = {}

    def predict(self, belief) -> np.ndarray:
        """A continuing track's predicted prior: its belief times the transition."""
        return np.asarray(belief, np.float64) @ self.trans

    @staticmethod
    def thresholds(prior) -> Tuple[int, ...]:
        return dac_thresholds(prior)

    def at_edge(self, prior) -> bool:
        """Whether a tail of ``prior`` sits where float32 rounding could move its DAC step."""
        tails = np.cumsum(np.asarray(prior, np.float64)[::-1])[::-1][1:] * 256.0
        return bool(np.any(np.abs(tails - np.floor(tails) - 0.5) < EDGE))

    def under(self, thresholds: Tuple[int, ...]) -> Network:
        """The network with the root's row at the DAC row of ``thresholds`` (cached)."""
        net = self._nets.get(thresholds)
        if net is None:
            bounds = (256,) + tuple(thresholds) + (0,)
            row = [(bounds[v] - bounds[v + 1]) / 256.0 for v in range(self.k)]
            doc = copy.deepcopy(self.doc)
            for n in doc["nodes"]:
                if n["name"] == self.root:
                    n["rows"] = [row]
            net = self._nets[thresholds] = Network(doc)
        return net

    def clamped(self, value: int) -> Network:
        """The network with the root fixed at ``value``."""
        return self.under((256,) * value + (0,) * (self.k - 1 - value))

    def belief(self, post, accepted: int, pred) -> np.ndarray:
        """The belief a frame leaves: its answered posterior over the root, or
        the prediction when it accepted no sample."""
        if accepted <= 0:
            return np.asarray(pred, np.float64)
        post = np.asarray(post, np.float64)
        if post.ndim == 1:                       # binary layout: P(q = 1) per query
            return np.asarray([1.0 - post[self.q], post[self.q]])
        return post[self.q, : self.k]
