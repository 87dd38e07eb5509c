"""Serve loop (``serve/engine.py``): the run_multi wall time outside the
launches (``ServeStats.total_ms`` minus the summed launch walls), per live
snapshot: chunk assembly, scheduling, commit, and waits on the producers.
Moves ``snapshots_per_s``."""


def read(rec):
    if not rec.live:
        return None
    return (rec.serve_ms - rec.launch_ms) / rec.live
