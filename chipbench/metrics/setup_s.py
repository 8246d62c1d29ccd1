"""Set-up seconds: process start to the first due frame (init, plans, compile or cache, warm-up)."""


def read(rec):
    return rec.setup_s
