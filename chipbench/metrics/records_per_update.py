"""Signed edge records the program's plans carried (``inc_edges`` +
``full_edges``) per update event applied, over the window's batches."""


def read(rec):
    events = sum(hi - lo for _, lo, hi, _, _ in rec.batches)
    if not events:
        return None
    return sum(b[0].inc_edges + b[0].full_edges for b in rec.batches) / events
