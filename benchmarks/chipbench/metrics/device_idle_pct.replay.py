"""Device, replay cells: share of the traced slice (one more replay pass)
in which no op ran on the device, ``1 - busy union / slice span``, in %.
Moves ``snapshots_per_s``."""
from chipbench.tracing import idle_pct


def read(rec):
    return idle_pct(rec.trace)
