"""Static-shape padding of snapshots for the device.

TPU programs have static shapes; the FPGA analogue in the paper is the fixed
BRAM allocation sized for the largest snapshot. We pad every snapshot into a
(n_pad, e_pad, k_max) bucket and carry masks. Padded edges point at a
dedicated sink row with coef 0, so no device-side branching is needed.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import numpy as np

from repro.graph.csr import LocalSnapshot, to_ell


def round_up(n: int, m: int) -> int:
    """Round ``n`` up to the next multiple of ``m`` (tile-alignment rule
    shared by the kernel row padding and the bucket machinery — the single
    copy; kernels/stream_fused.py and kernels/ops.py import it)."""
    return ((n + m - 1) // m) * m


def pow2_target(real: int, cap: int | None = None) -> int:
    """Next power of two >= ``real`` (>= 1), optionally capped.

    The padded sizes a jit cache is allowed to hold — log2 many per bucket.
    Shared by the serve chunk/batch padding and the plan front-end (one
    copy; serve/engine.py previously reimplemented it).

    Contract (changed after the silent-undersize bug): the result is
    ALWAYS >= ``real``. A ``cap`` smaller than ``real`` cannot be
    satisfied — a padding target below the true length would truncate
    live data — so it raises ``ValueError`` instead of silently returning
    ``cap``; a satisfiable cap clamps the power of two down to ``cap``
    (still >= ``real``, just no longer a power of two)."""
    if cap is not None and cap < real:
        raise ValueError(
            f"pow2_target: cap={cap} < real={real} — a padding target "
            "smaller than the real length would truncate live data")
    target = 1
    while target < real:
        target *= 2
    return max(min(target, cap), 1) if cap is not None else target


@jax.tree_util.register_dataclass
@dataclass
class PaddedSnapshot:
    """Device-ready snapshot. All arrays static-shape; a pytree."""

    # COO path (segment-sum reference)
    src: jax.Array        # (e_pad,) int32
    dst: jax.Array        # (e_pad,) int32
    coef: jax.Array       # (e_pad,) f32; 0 on padding
    edge_feat: jax.Array  # (e_pad, De) f32
    # ELL path (Pallas kernel)
    neigh_idx: jax.Array   # (n_pad, k_max) int32
    neigh_coef: jax.Array  # (n_pad, k_max) f32; 0 on padding
    neigh_eidx: jax.Array  # (n_pad, k_max) int32 into edge_feat
    # node data
    node_feat: jax.Array  # (n_pad, Din) f32
    node_mask: jax.Array  # (n_pad,) f32; 1 for real nodes
    renumber: jax.Array   # (n_pad,) int32 local->global (-1 on padding)
    n_nodes: jax.Array    # () int32
    n_edges: jax.Array    # () int32

    @property
    def n_pad(self) -> int:
        return self.node_feat.shape[0]

    @property
    def e_pad(self) -> int:
        return self.src.shape[0]

    @property
    def k_max(self) -> int:
        return self.neigh_idx.shape[1]


def _alloc_padded(lead: tuple, n_pad: int, e_pad: int, k_max: int,
                  din: int, de: int) -> PaddedSnapshot:
    """Uninitialised PaddedSnapshot leaves, each with ``lead`` axes first."""
    e, n = lead + (e_pad,), lead + (n_pad,)
    return PaddedSnapshot(
        src=np.empty(e, np.int32), dst=np.empty(e, np.int32),
        coef=np.empty(e, np.float32),
        edge_feat=np.empty(e + (de,), np.float32),
        neigh_idx=np.empty(n + (k_max,), np.int32),
        neigh_coef=np.empty(n + (k_max,), np.float32),
        neigh_eidx=np.empty(n + (k_max,), np.int32),
        node_feat=np.empty(n + (din,), np.float32),
        node_mask=np.empty(n, np.float32), renumber=np.empty(n, np.int32),
        n_nodes=np.empty(lead, np.int32), n_edges=np.empty(lead, np.int32))


def chunk_slab(depth: int, n_pad: int, e_pad: int, k_max: int, din: int,
               de: int) -> PaddedSnapshot:
    """Uninitialised room for ``depth`` padded snapshots of one bucket:
    every leaf has a leading ``depth`` axis (``n_nodes``/``n_edges`` are
    ``(depth,)``). Pad into its rows with ``pad_snapshot(out=slab_row(...))``;
    consecutive rows then stack along T as a view (``core.stack_time``)."""
    return _alloc_padded((depth,), n_pad, e_pad, k_max, din, de)


def slab_row(slab: PaddedSnapshot, i: int) -> PaddedSnapshot:
    """Row ``i`` of a ``chunk_slab``: a PaddedSnapshot of writable views
    (0-d ``n_nodes``/``n_edges``)."""
    return jax.tree.map(lambda a: a[i, ...], slab)


def pad_snapshot(
    ls: LocalSnapshot,
    feat_table: np.ndarray,
    n_pad: int,
    e_pad: int,
    k_max: int,
    out: PaddedSnapshot | None = None,
) -> PaddedSnapshot:
    """Pad a renumbered snapshot into the (n_pad, e_pad, k_max) bucket.

    ``feat_table`` is the global node-feature store (G, Din); the renumber
    table selects the active rows — the paper's DRAM->BRAM load, guided by
    the renumber table.

    ``out`` is an optional destination in that bucket (a ``slab_row``):
    every element of it is written, padding included, and it is returned.
    Without it the snapshot gets arrays of its own.
    """
    n, e = ls.n_nodes, ls.src.shape[0]
    if n > n_pad or e > e_pad:
        raise ValueError(f"snapshot ({n},{e}) exceeds bucket ({n_pad},{e_pad})")
    if out is None:
        out = _alloc_padded((), n_pad, e_pad, k_max, feat_table.shape[1],
                            ls.edge_feat.shape[1])
    elif (out.n_pad, out.e_pad, out.k_max) != (n_pad, e_pad, k_max):
        raise ValueError(f"out bucket ({out.n_pad},{out.e_pad},{out.k_max}) "
                         f"is not ({n_pad},{e_pad},{k_max})")
    # padded edges point at the sink row with coef 0
    for a, live, pad in ((out.src, ls.src, n_pad - 1),
                         (out.dst, ls.dst, n_pad - 1), (out.coef, ls.coef, 0),
                         (out.edge_feat, ls.edge_feat, 0)):
        a[:e] = live
        a[e:] = pad
    to_ell(ls, n_pad, k_max, out=(out.neigh_idx, out.neigh_coef,
                                  out.neigh_eidx))
    out.node_feat[:n] = feat_table[ls.renumber]
    out.node_feat[n:] = 0
    out.node_mask[:n] = 1.0
    out.node_mask[n:] = 0
    out.renumber[:n] = ls.renumber
    out.renumber[n:] = -1
    out.n_nodes[...] = n
    out.n_edges[...] = e
    return out


def empty_padded(n_pad: int, e_pad: int, k_max: int, din: int,
                 de: int) -> PaddedSnapshot:
    """An all-padding snapshot of the given bucket and feature dims.

    Running it through any dataflow engine is a no-op on the recurrent
    state (masks 0, renumber -1 so every scatter drops) and produces
    all-zero outputs — used to pad the tail of a stream chunk so the
    time-fused V3 kernel always sees a static T, and by the serve
    engine's bucket-calibration warmup.
    """
    return PaddedSnapshot(
        src=np.full(e_pad, n_pad - 1, np.int32),
        dst=np.full(e_pad, n_pad - 1, np.int32),
        coef=np.zeros(e_pad, np.float32),
        edge_feat=np.zeros((e_pad, de), np.float32),
        neigh_idx=np.zeros((n_pad, k_max), np.int32),
        neigh_coef=np.zeros((n_pad, k_max), np.float32),
        neigh_eidx=np.zeros((n_pad, k_max), np.int32),
        node_feat=np.zeros((n_pad, din), np.float32),
        node_mask=np.zeros(n_pad, np.float32),
        renumber=np.full(n_pad, -1, np.int32),
        n_nodes=np.int32(0),
        n_edges=np.int32(0),
    )


def empty_like_padded(ps: PaddedSnapshot) -> PaddedSnapshot:
    """An all-padding snapshot in the same bucket as ``ps``."""
    return empty_padded(ps.n_pad, ps.e_pad, ps.k_max, ps.node_feat.shape[1],
                        ps.edge_feat.shape[1])


def stack_streams(snaps: list[PaddedSnapshot]) -> PaddedSnapshot:
    """Stack independent streams along a leading batch axis (B, ...)."""
    return jax.tree.map(lambda *xs: np.stack(xs, axis=0), *snaps)


def unpad_snapshot(ps: PaddedSnapshot) -> dict:
    """Strip the padding from a PaddedSnapshot back to ragged host arrays.

    Inverse of ``pad_snapshot`` up to the ELL conversion: returns the live
    COO slice and per-node arrays (the round-trip contract the property
    tests assert). Keys: src, dst, coef, edge_feat, node_feat, renumber.
    """
    n = int(ps.n_nodes)
    e = int(ps.n_edges)
    return {
        "src": np.asarray(ps.src)[:e],
        "dst": np.asarray(ps.dst)[:e],
        "coef": np.asarray(ps.coef)[:e],
        "edge_feat": np.asarray(ps.edge_feat)[:e],
        "node_feat": np.asarray(ps.node_feat)[:n],
        "renumber": np.asarray(ps.renumber)[:n],
    }


def choose_bucket(n: int, e: int, k: int,
                  buckets: tuple[tuple[int, int, int], ...]) -> tuple[int, int, int]:
    """Pick the smallest bucket that fits (host-side; see serve/engine)."""
    for b in buckets:
        if n <= b[0] and e <= b[1] and k <= b[2]:
            return b
    raise ValueError(f"no bucket fits snapshot ({n},{e},k={k})")


def choose_bucket_batch(dims: "list[tuple[int, int, int]]",
                        buckets: tuple[tuple[int, int, int], ...]
                        ) -> tuple[int, int, int]:
    """Smallest bucket covering EVERY (n, e, k) in ``dims``.

    Used to co-bucket the snapshots of one multi-tenant stream chunk (and,
    transitively, the streams batched into one V3 launch): batching needs
    identical static shapes, so the chunk pays the max of its members —
    the multi-tenant padding tradeoff. Equal to the elementwise-max query
    against ``choose_bucket``, hence >= every member's individual bucket
    (the monotonicity property tests assert).
    """
    if not dims:
        raise ValueError("empty chunk: no dims to bucket")
    n = max(d[0] for d in dims)
    e = max(d[1] for d in dims)
    k = max(d[2] for d in dims)
    return choose_bucket(n, e, k, buckets)


def bucket_cost(bucket: tuple[int, int, int]) -> int:
    """Padded per-snapshot compute proxy for a bucket: ELL aggregation
    lanes (n_pad * k_max) plus the per-node transform rows (n_pad) — the
    work a snapshot pays when padded into the bucket, whatever its true
    size. Used by the promotion guard below."""
    n_pad, _, k_max = bucket
    return n_pad * (k_max + 1)


def promote_bucket_groups(groups: dict, buckets: tuple,
                          max_overhead: float, cost=bucket_cost) -> dict:
    """Cross-bucket batching via bucket promotion (multi-tenant grouper).

    ``groups`` maps bucket -> list of same-bucket stream chunks queued for
    one batched V3 launch each. A smaller-bucket group may be PROMOTED
    into the next-larger occupied bucket — its chunks re-pad to the bigger
    shape and join that launch — which trades padding overhead for one
    fewer device dispatch (the win batching exists for: small per-tenant
    chunks underutilize the device anyway). The guard: promotion happens
    only when bucket_cost(target) <= max_overhead * bucket_cost(own), so a
    tiny chunk is never inflated into a huge bucket just to save a launch.

    Returns a new groups dict; members keep their (sid, chunk, bucket)
    layout with the bucket re-tagged to the promotion target. Promotion is
    transitive up the chain (a promoted group can merge again) as long as
    every hop honours the guard against the member's ORIGINAL bucket.

    ``cost`` maps a bucket to its per-snapshot cost: the static padded-
    compute proxy ``bucket_cost`` by default, or measured per-bucket step
    times from the serve engine's warmup calibration (the adaptive guard).
    """
    order = {b: i for i, b in enumerate(buckets)}
    merged: dict = {b: list(members) for b, members in groups.items()}
    # ascending visit order: merges only move members into LATER buckets,
    # so every visited key is still present
    for b in sorted(merged, key=order.get):
        bigger = [b2 for b2 in merged if b2 != b and order[b2] > order[b]]
        if not bigger:
            continue
        target = min(bigger, key=order.get)
        # guard against each member's own bucket (promotion may chain)
        if any(cost(target) > max_overhead * cost(own)
               for _, _, own in merged[b]):
            continue
        merged[target] = merged[target] + merged[b]
        del merged[b]
    return {b: [(sid, chunk, b) for sid, chunk, _ in members]
            for b, members in merged.items()}


DEFAULT_BUCKETS = ((128, 512, 32), (320, 1024, 48), (640, 4096, 96))
