"""Whole step: model FLOPs of the live snapshots committed in the window
(``work/<family>.py``) over the window's seconds times the chip's peak
FLOP/s, in %. Moves ``snapshots_per_s``."""


def read(rec):
    if not rec.flops or not rec.window_s:
        return None
    return 100.0 * rec.flops / (rec.window_s * rec.peaks["flops_per_s"])
