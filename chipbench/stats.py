"""Percentiles of the benchmark's samples, one method for every reader."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """``numpy.percentile(samples, q)`` (linear between closest ranks) on plain floats."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q must be in [0, 100], got {q}")
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = (len(xs) - 1) * (q / 100.0)
    lo, hi = math.floor(rank), math.ceil(rank)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))
