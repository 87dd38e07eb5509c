"""Stream engine and glue: the least time the chip could take for the
traced slice's work, ``max(FLOPs / peak FLOP/s, bytes / peak bytes/s)``
with both counted from the live graph by ``work/<family>.py``, over the
device's busy time in the slice (every op of the served launches), in %.
``rec.trace_bound`` names the bound that applies. Moves
``snapshots_per_s``."""


def read(rec):
    t = rec.trace
    if not t or not t.get("busy_s") or not rec.trace_flops:
        return None
    floor_s = max(rec.trace_flops / rec.peaks["flops_per_s"],
                  rec.trace_bytes / rec.peaks["bytes_per_s"])
    return 100.0 * floor_s / t["busy_s"]
