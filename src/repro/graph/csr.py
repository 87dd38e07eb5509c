"""Graph renumbering + COO->CSR/ELL conversion (host side).

Renumbering (paper §IV-B): active global node ids are compacted to a dense
local index space [0, n_nodes) so device buffers are contiguous and gathers
are regular. The renumber table (local -> global) drives scatter-back into
the global node-state store, mirroring the paper's BRAM-address table.

Format conversion (paper §IV-B): COO is producer-friendly but irregular;
we build (a) a local-id COO with precomputed GCN normalization per edge for
the segment-sum reference path, and (b) an ELL (padded neighbor-list) layout
for the Pallas SpMM kernel — the TPU-friendly stand-in for the paper's
on-FPGA CSR, chosen because fixed-width rows map directly onto VMEM tiles.
Self-loops are added here so device code never branches.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.graph.coo import COOSnapshot


@dataclass
class LocalSnapshot:
    """Renumbered snapshot with GCN normalization, still host-side numpy."""

    src: np.ndarray        # (e',) int32 local ids (self-loops included)
    dst: np.ndarray        # (e',) int32
    coef: np.ndarray       # (e',) float32  1/sqrt(d_src * d_dst)
    edge_feat: np.ndarray  # (e', De) float32 (zeros for self-loops)
    renumber: np.ndarray   # (n,) int64 local -> global
    n_nodes: int
    t_index: int


def renumber_and_normalize(snap: COOSnapshot, symmetric: bool = True) -> LocalSnapshot:
    active = snap.active_nodes()                    # sorted unique global ids
    n = active.size
    # global -> local via searchsorted on the sorted active list
    src_l = np.searchsorted(active, snap.src).astype(np.int32)
    dst_l = np.searchsorted(active, snap.dst).astype(np.int32)
    de = snap.edge_feat.shape[1]
    if symmetric:
        # undirected message passing: add reverse edges (paper's GCN use)
        src2 = np.concatenate([src_l, dst_l])
        dst2 = np.concatenate([dst_l, src_l])
        ef2 = np.concatenate([snap.edge_feat, snap.edge_feat], axis=0)
    else:
        src2, dst2, ef2 = src_l, dst_l, snap.edge_feat
    # self loops (A + I)
    loops = np.arange(n, dtype=np.int32)
    src3 = np.concatenate([src2, loops])
    dst3 = np.concatenate([dst2, loops])
    ef3 = np.concatenate([ef2, np.zeros((n, de), np.float32)], axis=0)
    # symmetric normalization D^-1/2 (A+I) D^-1/2 over in-degree
    deg = np.bincount(dst3, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    coef = (dinv[src3] * dinv[dst3]).astype(np.float32)
    return LocalSnapshot(
        src=src3.astype(np.int32),
        dst=dst3.astype(np.int32),
        coef=coef,
        edge_feat=ef3.astype(np.float32),
        renumber=active.astype(np.int64),
        n_nodes=int(n),
        t_index=snap.t_index,
    )


def to_ell(ls: LocalSnapshot, n_pad: int, k_max: int, out: tuple | None = None
           ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Padded neighbor lists: for each dst node, up to k_max (src, coef).

    Returns (neigh_idx (n_pad, k_max) int32, neigh_coef (n_pad, k_max) f32,
    neigh_eidx (n_pad, k_max) int32 — index into the edge array, for edge
    features). Overflow beyond k_max raises: the bucket chooser must pick a
    k_max >= max in-degree (the "snapshot fits on-chip" contract).

    Fully vectorized (stable argsort by dst + per-dst rank via the
    run-start offset): this runs once per snapshot inside the serve
    producer thread, so a per-edge Python loop here throttles the §IV-D
    host/device overlap the engine is built around. Slot order per dst is
    original edge order (stable sort), identical to the sequential fill.

    ``out`` is an optional ``(idx, coe, eid)`` of writable arrays of those
    shapes to fill in place (every element is written) and return.
    """
    if out is None:
        out = (np.zeros((n_pad, k_max), np.int32),
               np.zeros((n_pad, k_max), np.float32),
               np.zeros((n_pad, k_max), np.int32))
    else:
        for a in out:
            a[...] = 0
    idx, coe, eid = out
    e = ls.src.shape[0]
    if e == 0:
        return idx, coe, eid
    order = np.argsort(ls.dst, kind="stable")
    dst_s = ls.dst[order]
    # rank within each dst run = position - first index of that dst value
    rank = np.arange(e) - np.searchsorted(dst_s, dst_s, side="left")
    over = rank >= k_max
    if over.any():
        # report the same node the sequential fill would have raised on:
        # the first edge (in original edge order) past its node's k_max
        bad = int(ls.dst[order[over].min()])
        raise ValueError(f"in-degree overflow at node {bad}: k_max={k_max}")
    idx[dst_s, rank] = ls.src[order]
    coe[dst_s, rank] = ls.coef[order]
    eid[dst_s, rank] = order
    return idx, coe, eid


def max_in_degree(ls: LocalSnapshot) -> int:
    return int(np.bincount(ls.dst, minlength=ls.n_nodes).max()) if ls.dst.size else 0
