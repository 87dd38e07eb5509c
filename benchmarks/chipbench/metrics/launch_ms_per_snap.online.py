"""Serve loop launches under open-loop load (``serve/scheduler.py``
ticks, ``_stage_group``): summed launch walls over live snapshots, from
``ServeStats``. A launch's wall covers state stacking, the host-to-device
copy, the device and the wait; it leaves out the time the loop spends
waiting for arrivals. Moves ``sojourn_p50_ms``."""


def read(rec):
    if not rec.live:
        return None
    return rec.launch_ms / rec.live
