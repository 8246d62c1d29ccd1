"""The comparison that decides ``correct``: served outputs against the reference.

Every compared frame has an evidence row, the posterior the system returned,
its accepted count and (replay) its per-query decisions.  With ``n`` the
configuration's stated stream length and ``P(e)`` the exact evidence
probability, a sound program's accepted count is Binomial(n, P(e)) and each
posterior entry is a binomial fraction of about ``n P(e)`` samples.  The
numbers compared hold the program to the stated ``n`` through the expected
sample count ``m = n P(e)``, never through the count the program reports:

* ``acc_z2``: mean over frames of ``(accepted - m)^2 / (n P(e) (1 - P(e)))``;
  1 for a sound run;
* ``post_z2``: mean over entries with ``m p (1 - p) >= 5`` of
  ``(q - p)^2 m / (p (1 - p))``; about 1 for a sound run and 4 for a stream a
  quarter as long;
* ``post_tail``: the largest ``m KL(q || p)`` over the entries of frames that
  accepted a sample, as a multiple
  of ``ln(2 N)`` for N entries (a Chernoff tail: a sound run reads about 1 or
  less at any N), so one altered answer shows;
* ``decision_mismatch``: decisions that differ from the MAP of the frame's
  own sample: the argmax (ties to the lowest value) of its per-value counts,
  recovered exactly as ``rint(posterior x accepted)`` (replay only; exact,
  limit 0).  Not the argmax of the returned float posterior: on a TPU the
  float division rounds an exact binary tie, 0.5, up by an ulp now and then,
  so that argmax differs at exact ties; those frames are counted apart
  (``posterior_tie_flips``, and how many of them are exact count ties,
  ``posterior_tie_flips_at_ties``; shown, not judged; PERF.md, Open
  questions);
* ``frames_missing``: frames due in the window with no answer (exact, limit 0).

Limits live in ``limits.json`` beside this file; PERF.md gives the readings
each was set from.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from chipbench.reference import Network

LIMITS = Path(__file__).resolve().parent / "limits.json"
NORMAL_MIN = 5.0   # m p (1-p) below this is left out of post_z2


def load_limits(path: Path = LIMITS) -> dict:
    with open(path) as f:
        return json.load(f)


def _kl(q, p):
    """Bernoulli KL(q || p) elementwise; inf where p is 0 or 1 and q differs."""
    q = np.clip(q, 0.0, 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        a = np.where(q > 0, q * np.log(q / p), 0.0)
        b = np.where(q < 1, (1 - q) * np.log((1 - q) / (1 - p)), 0.0)
    return np.nan_to_num(a + b, nan=np.inf)


class Comparison:
    """Accumulates the compared numbers over batches of frames of any network."""

    def __init__(self, n_bits: int):
        self.n_bits = int(n_bits)
        self.acc_sum = 0.0
        self.frames = 0
        self.z2_sum = 0.0
        self.z2_n = 0
        self.tail = 0.0
        self.tail_n = 0
        self.mismatch = 0
        self.tie_flips = 0
        self.tie_flips_at_ties = 0
        self.missing = 0

    def add(self, net: Network, ev, post, accepted, decisions=None) -> None:
        pat = net.pattern_index(ev)
        p_e = net.p_e[pat]
        m = self.n_bits * p_e
        acc = np.asarray(accepted, np.float64).reshape(-1)
        var = self.n_bits * p_e * (1 - p_e)
        with np.errstate(divide="ignore", invalid="ignore"):
            z2 = np.where(var > 0, (acc - m) ** 2 / var, np.where(acc == m, 0.0, np.inf))
        self.acc_sum += float(np.sum(z2))
        self.frames += len(acc)
        got, val = net.entries(post)
        exact = np.stack([net.post[pat, qi, v] for qi, v in val], axis=-1)
        mm = np.broadcast_to(m[:, None], got.shape)
        pq = exact * (1 - exact)
        normal = mm * pq >= NORMAL_MIN
        self.z2_sum += float(np.sum((got - exact)[normal] ** 2 * mm[normal] / pq[normal]))
        self.z2_n += int(normal.sum())
        # a frame that accepted nothing has no posterior (the system returns
        # zeros); its accepted count is judged by acc_z2
        live = (mm > 0) & (acc[:, None] > 0)
        if live.any():
            self.tail = max(self.tail, float(np.max(mm[live] * _kl(got[live], exact[live]))))
        self.tail_n += int(live.sum())
        if decisions is not None:
            dec = np.asarray(decisions)
            self.mismatch += int(np.sum(dec != net.count_argmax(post, acc)))
            flips = dec != net.argmax(post)
            self.tie_flips += int(np.sum(flips))
            self.tie_flips_at_ties += int(np.sum(flips & net.count_tied(post, acc)))

    def readings(self) -> dict:
        nan = float("nan")
        return {
            "acc_z2": self.acc_sum / self.frames if self.frames else nan,
            "post_z2": self.z2_sum / self.z2_n if self.z2_n else nan,
            "post_tail": self.tail / np.log(2 * self.tail_n) if self.tail_n else nan,
            "decision_mismatch": self.mismatch,
            "frames_missing": self.missing,
        }


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: every number at or under its limit.

    A missing reading (nothing compared: NaN, shown as null) is not correct.
    """
    shown = {}
    ok = True
    for name, limit in limits.items():
        v = readings[name]
        shown[name] = {"value": v if v == v else None, "limit": limit}
        if not (v <= limit):   # NaN fails too
            ok = False
    return ok, shown
