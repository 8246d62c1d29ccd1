"""Frames pushed to a later launch because their track was already in it, per 1000 tracked frames.

``track.deferred`` x 1000 over ``track.carried`` + ``track.fresh``, the
driver's counts in the window.
"""


def read(rec):
    counts = getattr(rec, "program_counts", None)
    if not counts:
        return None
    frames = counts.get("track.carried", 0) + counts.get("track.fresh", 0)
    return 1000.0 * counts.get("track.deferred", 0) / frames if frames else None
