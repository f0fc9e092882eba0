"""Mean ``BatchStats.plan_time_s`` over the batches that carried the
window's events, in milliseconds (the program's own host timer)."""


def read(rec):
    if not rec.batches:
        return None
    return 1e3 * sum(b[0].plan_time_s for b in rec.batches) / len(rec.batches)
