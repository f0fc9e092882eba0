"""95th percentile, over every update event due in the window, of the time
from its due time to the return of the ``apply_batch`` that applied it."""
import numpy as np


def read(rec):
    lat = rec.ev_visible - rec.ev_due
    if lat.size == 0 or np.isnan(lat).any():
        return None
    return float(np.percentile(lat, 95))
