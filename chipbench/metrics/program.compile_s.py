"""Seconds of jax.monitoring /jax/core/compile/*_duration events during set-up (trace, lower, compile)."""


def read(rec):
    if rec.compile_setup_s != rec.compile_setup_s:   # NaN: never measured
        return None
    return rec.compile_setup_s
