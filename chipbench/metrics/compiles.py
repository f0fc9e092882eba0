"""XLA executables built inside the measured window (compiled, or read from
the persistent cache), counted through ``jax.monitoring``."""


def read(rec):
    return float(rec.compiles)
