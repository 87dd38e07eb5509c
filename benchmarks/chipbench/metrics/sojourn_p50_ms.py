"""Open-loop cells, end to end: the median, over every snapshot due in
the window (drained ones included), of the time from its due time on the
arrival schedule to its commit (host clock)."""
import numpy as np


def read(rec):
    if not rec.sojourn_ms:
        return None
    return float(np.percentile(rec.sojourn_ms, 50))
