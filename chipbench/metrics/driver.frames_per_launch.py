"""Frames a launch: the driver's ``frames_out`` count over its ``launches`` count, in the window."""


def read(rec):
    counts = getattr(rec, "program_counts", None)
    if not counts or not counts.get("launches"):
        return None
    return counts.get("frames_out", 0) / counts["launches"]
