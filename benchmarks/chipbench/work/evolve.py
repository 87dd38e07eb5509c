"""Least work of EvolveGCN-O (see ``reference/evolve.py``), counted from
the live graph: ``n`` active nodes, ``e`` directed input edges, so ``a =
2e + n`` aggregated edges.

A multiply-add counts 2 FLOPs; elementwise work is left out (under 1%).
Per layer (din -> dout): the edge term in its cheaper order (features
aggregated, then projected), the aggregation, the transform, and the
matrix GRU that evolves W (dout rows of width din through two
din x 3din transforms), which every live snapshot pays whatever its size.
Bytes are float32/int32, each moved once.
"""
from __future__ import annotations

from chipbench.reference.evolve import layer_dims

F32 = 4


def snapshot_flops(m: dict, n: int, e: int) -> int:
    de = m["edge_dim"]
    a = 2 * e + n
    total = 0
    for din, dout in layer_dims(m):
        total += (a * de + n * de * din   # edge term
                  + a * din               # aggregation
                  + n * din * dout        # transform
                  + 2 * dout * din * 3 * din)  # matrix GRU
    return 2 * total


def snapshot_bytes(m: dict, n: int, e: int) -> int:
    """The snapshot's inputs (edge endpoints and features, the active
    nodes' ids and feature rows) and its outputs."""
    return F32 * (e * (2 + m["edge_dim"]) + n * (1 + m["in_dim"])
                  + n * m["out_dim"])


def weight_bytes(m: dict) -> int:
    """Parameters other than the evolving matrices, read once per
    launch: biases, edge projections, GRU weights."""
    de = m["edge_dim"]
    return F32 * sum(dout + de * din + 2 * din * 3 * din + 3 * din
                     for din, dout in layer_dims(m))


def state_bytes(m: dict, rows: int) -> int:
    """One stream's evolving matrices, read once and written once in a
    launch; independent of the rows the graph touches."""
    del rows
    return F32 * 2 * sum(din * dout for din, dout in layer_dims(m))
