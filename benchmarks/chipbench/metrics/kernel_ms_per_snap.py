"""Stream engine (``kernels/stream_fused.py``): device time of the
``stream_engine_*`` kernel ops of the traced slice, per live snapshot
served in the slice. Moves ``snapshots_per_s``."""


def read(rec):
    t = rec.trace
    if not t or not t.get("kernel_s") or not rec.trace_live:
        return None
    return 1e3 * t["kernel_s"] / rec.trace_live
