"""Decisions fetched to the host in the window, over the window (first dispatch to last fetch)."""


def read(rec):
    if not rec.decisions or not rec.window_s > 0:
        return None
    return rec.decisions / rec.window_s
