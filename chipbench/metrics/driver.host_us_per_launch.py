"""Driver host time a launch, us: the summed ``pack``, ``dispatch`` and ``harvest`` spans over the ``launch[n]`` spans.

``harvest`` runs from the host's first look at a launch's results to their
last conversion, so it holds the wait for the device and the copies back.
"""

PARTS = ("pack", "dispatch", "harvest")


def read(rec):
    spans = getattr(rec, "program_spans", None)
    if not spans:
        return None
    launches = sum(1 for name, _, _ in spans if name.startswith("launch["))
    host = sum(t1 - t0 for name, t0, t1 in spans if name in PARTS)
    return 1e6 * host / launches if launches and host > 0 else None
