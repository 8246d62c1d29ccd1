"""Median decision latency, ms: from the time a frame's camera tick was due to the host receiving its result.

Over every frame due in the window; a frame not answered ``OK`` in time counts
above every answered one (the mode's ``latency_ms``).
"""

from chipbench.stats import percentile


def read(rec):
    lat = getattr(rec, "latency_ms", None)
    return percentile(lat, 50) if lat else None
