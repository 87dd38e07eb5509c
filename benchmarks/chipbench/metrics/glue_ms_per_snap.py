"""Model glue and dispatch (``core/*.py``, ``kernels/ops.py``): device
time of every op of the traced slice that is not the stream-engine
kernel, per live snapshot served in the slice. Moves ``snapshots_per_s``."""


def read(rec):
    t = rec.trace
    if not t or t.get("glue_s") is None or not rec.trace_live:
        return None
    return 1e3 * t["glue_s"] / rec.trace_live
