"""99th percentile of the load generator's lag, ms: a frame's submit time less its tick's due time."""

from chipbench.stats import percentile


def read(rec):
    lag = getattr(rec, "lag_ms", None)
    return percentile(lag, 99) if lag else None
