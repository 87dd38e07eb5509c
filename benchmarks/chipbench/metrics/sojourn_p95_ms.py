"""Serve loop queueing under open-loop load: the 95th percentile (linear
interpolation) over every snapshot due in the window (drained ones
included, not a median of chunks) of the time from its due time on the
arrival schedule to its commit (host clock). Moves ``sojourn_p50_ms``:
a tick that stalls lets the ready set grow, the next launch pads more
slots, and the backlog feeds on itself until it drains."""
import numpy as np


def read(rec):
    if not rec.sojourn_ms:
        return None
    return float(np.percentile(rec.sojourn_ms, 95))
