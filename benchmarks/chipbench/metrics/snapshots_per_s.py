"""Replay cells, end to end: live snapshots committed in the window over
the window's seconds, all the work over all the time (host clock), with
the passes back to back."""


def read(rec):
    if not rec.window_s or not rec.live:
        return None
    return rec.live / rec.window_s
