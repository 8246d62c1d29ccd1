"""95th percentile of the decision latency, ms: tick due to result received, as ``latency_p50_ms``."""

from chipbench.stats import percentile


def read(rec):
    lat = getattr(rec, "latency_ms", None)
    return percentile(lat, 95) if lat else None
