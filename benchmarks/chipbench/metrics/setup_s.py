"""Process start to the first measured snapshot: imports, the graph
stream, the weights, the server, and the warm-up with any compilation
(host clock)."""


def read(rec):
    return rec.setup_s
