"""Host prep (``graph/padding.py`` and ``graph/csr.py``, run in the serve
producer threads): mean ``ServeStats.preprocess_ms`` per snapshot over the
window. Moves ``snapshots_per_s``."""


def read(rec):
    if not rec.prep_count:
        return None
    return rec.prep_ms / rec.prep_count
