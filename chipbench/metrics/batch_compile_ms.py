"""Mean ``BatchStats.compile_time_s`` over the batches that carried the
window's events, in milliseconds: seconds of the XLA executables built
inside each batch's graph, plan and exec spans (the program's compile
tally, through ``jax.monitoring``).  None where the program keeps no such
field."""


def read(rec):
    vals = [getattr(b[0], "compile_time_s", None) for b in rec.batches]
    if not vals or None in vals:
        return None
    return 1e3 * sum(vals) / len(vals)
