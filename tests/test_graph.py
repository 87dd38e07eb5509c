"""Graph substrate: slicing, renumbering, format conversion, padding."""
import jax
import numpy as np
import pytest

from repro.configs.dgnn import BC_ALPHA, UCI
from repro.core import stack_time
from repro.graph import (
    choose_bucket,
    empty_like_padded,
    generate_temporal_graph,
    max_in_degree,
    pad_snapshot,
    renumber_and_normalize,
    slice_snapshots,
    snapshot_stats,
    to_ell,
)
from repro.graph.padding import chunk_slab, slab_row


@pytest.fixture(scope="module")
def bc():
    tg, ft = generate_temporal_graph(BC_ALPHA)
    return tg, ft, slice_snapshots(tg, 1.0)


def test_slice_covers_all_edges(bc):
    tg, _, snaps = bc
    assert sum(s.n_edges for s in snaps) == tg.n_edges


def test_snapshot_stats_match_table3_scale(bc):
    _, _, snaps = bc
    st = snapshot_stats(snaps)
    # Table III: BC-Alpha avg 107 nodes / 232 edges, max 578 / 1686
    assert 70 <= st["avg_nodes"] <= 160
    assert 150 <= st["avg_edges"] <= 350
    assert st["max_nodes"] <= BC_ALPHA.max_nodes
    assert st["max_edges"] <= BC_ALPHA.max_edges
    assert st["snapshots"] == BC_ALPHA.snapshots


def test_renumbering_is_dense_and_invertible(bc):
    _, _, snaps = bc
    ls = renumber_and_normalize(snaps[3])
    # local ids form a dense [0, n) space
    assert ls.src.max() < ls.n_nodes and ls.dst.max() < ls.n_nodes
    # renumber table maps back to the original global ids
    orig = set(np.concatenate([snaps[3].src, snaps[3].dst]).tolist())
    assert set(ls.renumber.tolist()) == orig
    # sorted + unique (searchsorted contract)
    assert np.all(np.diff(ls.renumber) > 0)


def test_gcn_normalization_rows(bc):
    _, _, snaps = bc
    ls = renumber_and_normalize(snaps[0])
    # symmetric normalization: sum_j coef(i<-j) * sqrt(d_j/d_i) == 1; check
    # the weaker invariant that the self-loop coef is 1/d for isolated nodes
    deg = np.bincount(ls.dst, minlength=ls.n_nodes)
    assert (deg >= 1).all()  # every node has at least the self-loop
    assert (ls.coef > 0).all()


def test_ell_matches_coo(bc):
    _, _, snaps = bc
    ls = renumber_and_normalize(snaps[1])
    k = max_in_degree(ls)
    idx, coef, eidx = to_ell(ls, 640, k)
    # edge multiset preserved: sum of coefs equal
    assert np.isclose(coef.sum(), ls.coef.sum(), rtol=1e-5)
    # per-node in-degree preserved
    fill = (coef != 0).sum(axis=1)
    deg = np.bincount(ls.dst, minlength=640)
    # zero-coef edges are legal but rare; degree bound must hold
    assert (fill <= deg).all()


def test_ell_overflow_raises(bc):
    _, _, snaps = bc
    ls = renumber_and_normalize(snaps[0])
    with pytest.raises(ValueError):
        to_ell(ls, 640, 1)


def test_pad_snapshot_shapes_and_masks(bc):
    _, ft, snaps = bc
    ls = renumber_and_normalize(snaps[0])
    ps = pad_snapshot(ls, ft, 640, 4096, 64)
    assert ps.node_feat.shape == (640, ft.shape[1])
    assert ps.node_mask.sum() == ls.n_nodes
    assert int(ps.n_nodes) == ls.n_nodes
    # padded edges must be dead (coef 0)
    e = ls.src.shape[0]
    assert np.all(np.asarray(ps.coef)[e:] == 0)
    # renumber padding marked -1
    assert np.all(np.asarray(ps.renumber)[ls.n_nodes:] == -1)


def test_bucket_overflow_raises(bc):
    _, ft, snaps = bc
    ls = renumber_and_normalize(snaps[0])
    with pytest.raises(ValueError):
        pad_snapshot(ls, ft, ls.n_nodes - 1, 4096, 64)


BUCKETS = ((128, 512, 32), (320, 1024, 48), (640, 4096, 96))


def test_choose_bucket_smallest_fit():
    assert choose_bucket(100, 400, 16, BUCKETS) == (128, 512, 32)
    # one dimension overflowing the small bucket promotes the whole snapshot
    assert choose_bucket(100, 400, 33, BUCKETS) == (320, 1024, 48)
    assert choose_bucket(100, 2000, 16, BUCKETS) == (640, 4096, 96)


def test_choose_bucket_exact_fit_boundary():
    # <= is inclusive: a snapshot exactly at the bucket limits still fits
    assert choose_bucket(128, 512, 32, BUCKETS) == (128, 512, 32)
    assert choose_bucket(640, 4096, 96, BUCKETS) == (640, 4096, 96)
    # one past the boundary promotes / raises
    assert choose_bucket(129, 512, 32, BUCKETS) == (320, 1024, 48)


def test_choose_bucket_no_fit_raises():
    with pytest.raises(ValueError):
        choose_bucket(641, 8, 8, BUCKETS)
    with pytest.raises(ValueError):
        choose_bucket(8, 8, 97, BUCKETS)


def test_empty_like_padded_is_noop_snapshot(bc):
    _, ft, snaps = bc
    ls = renumber_and_normalize(snaps[0])
    ps = pad_snapshot(ls, ft, 640, 4096, 64)
    empty = empty_like_padded(ps)
    assert empty.node_feat.shape == ps.node_feat.shape
    assert empty.edge_feat.shape == ps.edge_feat.shape
    assert int(empty.n_nodes) == 0
    assert np.all(np.asarray(empty.node_mask) == 0)
    assert np.all(np.asarray(empty.renumber) == -1)
    assert np.all(np.asarray(empty.neigh_coef) == 0)


def _slab_rows(ft, lss, bucket=(640, 4096, 64)):
    """``lss`` padded into the consecutive rows of one fresh chunk slab."""
    slab = chunk_slab(len(lss), *bucket, ft.shape[1],
                      lss[0].edge_feat.shape[1])
    return [pad_snapshot(ls, ft, *bucket, out=slab_row(slab, i))
            for i, ls in enumerate(lss)]


# how each case picks the steps to stack from two slabs a and b of 8 rows
# (and from snapshots padded on their own)
STACK_CASES = {
    "consecutive_slab_rows": lambda a, b, fresh: a[2:6],
    "repeated_last_row": lambda a, b, fresh: a[:3] + [a[2]],
    "rows_of_two_slabs": lambda a, b, fresh: a[6:] + b[:2],
    "rows_out_of_order": lambda a, b, fresh: [a[1], a[0], a[2]],
    "fresh_arrays": lambda a, b, fresh: fresh[:4],
}


@pytest.mark.parametrize("case", sorted(STACK_CASES))
def test_stack_time_views_consecutive_slab_rows_else_copies(bc, case):
    """Consecutive rows of one slab, in order, stack along T as a view of
    the slab; every other input is copied. Both equal ``np.stack`` leaf
    for leaf."""
    _, ft, snaps = bc
    lss = [renumber_and_normalize(s) for s in snaps[:16]]
    a, b = _slab_rows(ft, lss[:8]), _slab_rows(ft, lss[8:])
    fresh = [pad_snapshot(ls, ft, 640, 4096, 64) for ls in lss[:4]]
    steps = STACK_CASES[case](a, b, fresh)
    got = jax.tree.leaves(stack_time(steps))
    want = jax.tree.leaves(jax.tree.map(lambda *xs: np.stack(xs), *steps))
    firsts = jax.tree.leaves(steps[0])
    assert len(got) == len(want) == 12
    for g, w, first in zip(got, want, firsts):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
        assert np.shares_memory(g, first) == (case == "consecutive_slab_rows")


def test_pad_snapshot_into_a_dirty_slot_equals_a_fresh_pad(bc):
    """A slot that held a larger snapshot, padded again with a smaller
    one, equals a fresh ``pad_snapshot`` of the smaller one everywhere:
    the sink-row ``src``/``dst``, the ``-1`` renumber and the zero masks
    and coefficients of the padding included. The slab's other row is
    left as it was."""
    _, ft, snaps = bc
    lss = sorted((renumber_and_normalize(s) for s in snaps),
                 key=lambda ls: ls.src.shape[0])
    small, big = lss[0], lss[-1]
    assert big.src.shape[0] > small.src.shape[0]
    assert big.n_nodes > small.n_nodes
    slab = chunk_slab(2, 640, 4096, 64, ft.shape[1],
                      small.edge_feat.shape[1])
    for leaf in jax.tree.leaves(slab):
        leaf[...] = 7  # no element may keep what the slab held
    slot = slab_row(slab, 1)
    pad_snapshot(big, ft, 640, 4096, 64, out=slot)
    got = pad_snapshot(small, ft, 640, 4096, 64, out=slot)
    assert got is slot
    want = pad_snapshot(small, ft, 640, 4096, 64)
    for name in ("src", "dst", "coef", "edge_feat", "neigh_idx",
                 "neigh_coef", "neigh_eidx", "node_feat", "node_mask",
                 "renumber", "n_nodes", "n_edges"):
        g, w = getattr(got, name), getattr(want, name)
        assert g.dtype == w.dtype, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    n, e = small.n_nodes, small.src.shape[0]
    assert np.all(slot.src[e:] == 639) and np.all(slot.dst[e:] == 639)
    assert np.all(slot.renumber[n:] == -1)
    assert not slot.node_mask[n:].any() and not slot.coef[e:].any()
    assert not slot.neigh_coef[n:].any()
    for leaf in jax.tree.leaves(slab_row(slab, 0)):
        assert np.all(leaf == 7)
