"""95th percentile, over every read due in the window, of the time from its
due time to the return of the ``serve_reads`` call that served it."""
import numpy as np


def read(rec):
    lat = rec.rd_served - rec.rd_due
    if lat.size == 0 or np.isnan(lat).any():
        return None
    return float(np.percentile(lat, 95))
