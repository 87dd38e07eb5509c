"""Device, open-loop cells: share of the traced slice (a few seconds of
the cell's own arrivals) in which no op ran on the device,
``1 - busy union / slice span``, in %. Moves ``sojourn_p50_ms``."""
from chipbench.tracing import idle_pct


def read(rec):
    return idle_pct(rec.trace)
