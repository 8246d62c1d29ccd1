"""Tracker host time a launch, us: the summed ``track.index`` spans over the ``launch[n]`` spans.

``track.index`` covers building a launch's table-row index and fresh-track
flags, inside the driver's ``pack``.
"""


def read(rec):
    spans = getattr(rec, "program_spans", None)
    if not spans:
        return None
    launches = sum(1 for name, _, _ in spans if name.startswith("launch["))
    index = [t1 - t0 for name, t0, t1 in spans if name == "track.index"]
    return 1e6 * sum(index) / launches if launches and index else None
