"""Serve loop batching: padded launch slots over all launch slots,
``padded / (live + padded)`` from ``ServeStats``, in %. Moves
``snapshots_per_s``."""


def read(rec):
    slots = rec.live + rec.padded
    if not slots:
        return None
    return 100.0 * rec.padded / slots
