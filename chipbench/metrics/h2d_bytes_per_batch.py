"""Mean ``BatchStats.h2d_bytes`` over the batches that carried the window's
events: bytes of the packed plan copied host→device for each batch (the
program's counter in its ``repro/device_put`` span).  None where the
program keeps no such field."""


def read(rec):
    vals = [getattr(b[0], "h2d_bytes", None) for b in rec.batches]
    if not vals or None in vals:
        return None
    return sum(vals) / len(vals)
