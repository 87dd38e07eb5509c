"""Least work of GCRN-M2 (see ``reference/gcrn.py``), counted from the
live graph: ``n`` active nodes, ``e`` directed input edges, so ``a = 2e +
n`` aggregated edges (both directions plus a self-loop per node).

A multiply-add counts 2 FLOPs. Elementwise work (bias adds, the gate
nonlinearities, the cell update: about 10 n * hidden) is left out: under
1% of the total at the published widths. The edge term is counted in its
cheaper order, edge features aggregated first (a * edge_dim) and then
projected (n * edge_dim * in_dim), so that no layout of the program can
do less than is counted. Bytes are float32/int32, each moved once.
"""
from __future__ import annotations

F32 = 4


def snapshot_flops(m: dict, n: int, e: int) -> int:
    din, hd, de, dout = m["in_dim"], m["hidden"], m["edge_dim"], m["out_dim"]
    a = 2 * e + n
    return 2 * (a * de + n * de * din      # edge term
                + a * din + a * hd        # Xa, Ha aggregations
                + n * (din + hd) * 4 * hd  # gate transforms
                + n * hd * dout)          # output head


def snapshot_bytes(m: dict, n: int, e: int) -> int:
    """The snapshot's inputs (edge endpoints and features, the active
    nodes' ids and feature rows) and its outputs."""
    return F32 * (e * (2 + m["edge_dim"]) + n * (1 + m["in_dim"])
                  + n * m["out_dim"])


def weight_bytes(m: dict) -> int:
    """Parameters, read once per launch."""
    din, hd, de, dout = m["in_dim"], m["hidden"], m["edge_dim"], m["out_dim"]
    return F32 * ((din + hd + 1) * 4 * hd + (hd + 1) * dout + de * din)


def state_bytes(m: dict, rows: int) -> int:
    """One stream's h and c rows that a launch touches, read once and
    written once."""
    return F32 * 2 * 2 * rows * m["hidden"]
