"""Mean ``BatchStats.hook_time_s`` over the batches that carried the
window's events, in milliseconds: the part of ``exec_ms`` spent capturing
the serving front-end's undo pre-images before dispatch (the program's
``repro/undo_capture`` span).  None where the program keeps no such field."""


def read(rec):
    vals = [getattr(b[0], "hook_time_s", None) for b in rec.batches]
    if not vals or None in vals:
        return None
    return 1e3 * sum(vals) / len(vals)
