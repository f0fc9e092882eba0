"""Mean ``BatchStats.pack_time_s`` over the batches that carried the
window's events, in milliseconds: the part of ``plan_ms`` spent packing the
plan into its transfer buffers (the program's ``repro/plan/pack`` span).
None where the program keeps no such field."""


def read(rec):
    vals = [getattr(b[0], "pack_time_s", None) for b in rec.batches]
    if not vals or None in vals:
        return None
    return 1e3 * sum(vals) / len(vals)
