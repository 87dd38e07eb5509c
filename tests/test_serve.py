"""Serving engine: host/device task split, double-buffered stream == offline."""
import jax
import numpy as np

from repro.configs.dgnn import DGNN_CONFIGS, GCRN_M2, UCI
from repro.core import build_model, run_stream, stack_time
from repro.graph import (
    generate_temporal_graph,
    pad_snapshot,
    renumber_and_normalize,
    slice_snapshots,
)
from repro.serve import SnapshotServer


def test_snapshot_server_matches_offline():
    tg, ft = generate_temporal_graph(UCI)
    snaps = slice_snapshots(tg, 1.0)[:6]
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes, mode="v2")
    params, state = srv.init(jax.random.PRNGKey(0))
    _, outs, stats = srv.run(params, state, snaps)
    assert len(outs) == 6
    assert stats.phase_ms["serve.device_wait"] > 0
    assert len(stats.preprocess_ms) == 6
    # offline scan over the same padded stream gives identical outputs
    model = build_model(GCRN_M2, n_global=tg.n_global_nodes)
    pads = [pad_snapshot(renumber_and_normalize(s), ft, srv.n_pad, srv.e_pad,
                         srv.k_max) for s in snaps]
    st = model.init_state(params, mode="v2")
    _, offline = run_stream(model, params, st, stack_time(pads), mode="v2")
    for t in range(6):
        np.testing.assert_allclose(outs[t], np.asarray(offline)[t], atol=1e-5)


def test_snapshot_server_v3_stream_matches_offline():
    """The v3 fast path batches same-bucket snapshots into fixed-T chunks
    for the time-fused stream kernel (tail padded with no-op snapshots);
    outputs must equal the offline baseline scan."""
    tg, ft = generate_temporal_graph(UCI)
    snaps = slice_snapshots(tg, 1.0)[:6]
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes, mode="v3",
                         stream_chunk=4)  # 6 snaps -> 4 + padded tail of 2
    params, state = srv.init(jax.random.PRNGKey(0))
    final_state, outs, stats = srv.run(params, state, snaps)
    assert len(outs) == 6
    assert stats.phase_ms["serve.device_wait"] > 0
    model = build_model(GCRN_M2, n_global=tg.n_global_nodes)
    pads = [pad_snapshot(renumber_and_normalize(s), ft, srv.n_pad, srv.e_pad,
                         srv.k_max) for s in snaps]
    st = model.init_state(params, mode="baseline")
    offline_state, offline = run_stream(model, params, st, stack_time(pads),
                                        mode="baseline")
    for t in range(6):
        np.testing.assert_allclose(outs[t], np.asarray(offline)[t], atol=1e-5)
    # the padded no-op tail must not disturb the recurrent state
    np.testing.assert_allclose(np.asarray(final_state["h"]),
                               np.asarray(offline_state["h"]), atol=1e-5)


def test_snapshot_server_spans_two_buckets():
    """Bucketed padding: a stream whose snapshots land in different buckets
    still produces offline-identical outputs (one compiled step per bucket,
    outputs shaped per bucket)."""
    from repro.graph import choose_bucket, max_in_degree

    tg, ft = generate_temporal_graph(UCI)
    snaps = slice_snapshots(tg, 1.0)[:8]
    buckets = ((256, 1024, 48), (640, 4096, 64))
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes,
                         mode="v2", buckets=buckets)
    params, state = srv.init(jax.random.PRNGKey(0))
    _, outs, _ = srv.run(params, state, snaps)
    assert len(outs) == 8
    # the stream must genuinely exercise both bucket sizes
    sizes = {o.shape[0] for o in outs}
    assert sizes == {256, 640}, sizes
    # offline replay with the same per-snapshot bucket choice
    model = build_model(GCRN_M2, n_global=tg.n_global_nodes)
    st = model.init_state(params, mode="v2")
    for t, s in enumerate(snaps):
        ls = renumber_and_normalize(s)
        b = choose_bucket(ls.n_nodes, ls.src.shape[0], max_in_degree(ls),
                          buckets)
        ps = pad_snapshot(ls, ft, *b)
        st, out = model.step(params, st, ps, mode="v2")
        np.testing.assert_allclose(outs[t], np.asarray(out), atol=1e-5,
                                   err_msg=f"t={t} bucket={b}")


def _forbid_per_step(srv):
    """Make the per-snapshot jitted step unusable: any fallback off the
    stream path fails loudly instead of silently degrading."""
    def boom(*a, **k):
        raise AssertionError("per-snapshot fallback taken — v3 must route "
                             "through the stream kernel")
    srv._step = boom


def test_snapshot_server_v3_evolvegcn_takes_stream_path():
    """EvolveGCN mode="v3" runs the weights-resident stream kernel in the
    server — NO per-snapshot fallback (regression: PR 2 fell back to v1
    stepping) — and the chunk's no-op tail snapshots must leave the
    evolving-weight state untouched (the final state equals the offline
    v1 scan over the LIVE snapshots only)."""
    cfg = DGNN_CONFIGS["evolvegcn"]
    tg, ft = generate_temporal_graph(UCI)
    # 7 snaps, stream_chunk=4 -> chunks of T=4 and T=3; the second pads to
    # the next power of two with ONE no-op tail snapshot (pow2(3) == 4),
    # so the single-tenant tail path is genuinely exercised.
    snaps = slice_snapshots(tg, 1.0)[:7]
    srv = SnapshotServer(cfg, ft, n_global=tg.n_global_nodes, mode="v3",
                         stream_chunk=4)
    _forbid_per_step(srv)
    params, state = srv.init(jax.random.PRNGKey(0))
    final_state, outs, _ = srv.run(params, state, snaps)
    assert len(outs) == 7
    model = build_model(cfg)
    pads = [pad_snapshot(renumber_and_normalize(s), ft, srv.n_pad, srv.e_pad,
                         srv.k_max) for s in snaps]
    st = model.init_state(params, mode="baseline")
    _, offline = run_stream(model, params, st, stack_time(pads),
                            mode="baseline")
    for t in range(7):
        np.testing.assert_allclose(outs[t], np.asarray(offline)[t], atol=1e-5)
    # evolving-weight state: equal to the v1 scan over the 7 live
    # snapshots — if the no-op tail step had evolved the weights, or the
    # kernel double-evolved at its first step, this diverges.
    st1 = model.init_state(params, mode="v1")
    off_state, _ = run_stream(model, params, st1, stack_time(pads),
                              mode="v1")
    for i, (got, want) in enumerate(zip(final_state["weights"],
                                        off_state["weights"])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, err_msg=f"weights[{i}]")


def test_snapshot_server_no_fit_bucket_raises():
    """A snapshot that fits no bucket must raise in run(), not hang the
    consumer when the producer thread dies (regression)."""
    import pytest

    tg, ft = generate_temporal_graph(UCI)
    snaps = slice_snapshots(tg, 1.0)[:2]
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes, mode="v2",
                         buckets=((8, 8, 2),))
    params, state = srv.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="no bucket fits"):
        srv.run(params, state, snaps)


def _offline_outputs(cfg, tg, ft, params, snaps,
                     n_pad=640, e_pad=4096, k_max=64):
    """Ground truth: the baseline scan over one client's padded stream."""
    model = build_model(cfg, n_global=tg.n_global_nodes)
    pads = [pad_snapshot(renumber_and_normalize(s), ft, n_pad, e_pad, k_max)
            for s in snaps]
    st = model.init_state(params, mode="baseline")
    return run_stream(model, params, st, stack_time(pads), mode="baseline")


def test_run_multi_batched_v3_matches_per_stream_offline():
    """Multi-tenant batched V3: three clients with different streams and
    UNEVEN lengths (forcing no-op tail snapshots inside batched chunks).
    Every client's outputs must equal its own offline baseline, in its own
    snapshot order, and its final state must be undisturbed by the other
    tenants and by the no-op tails."""
    tg, ft = generate_temporal_graph(UCI)
    all_snaps = slice_snapshots(tg, 1.0)
    streams = {"a": all_snaps[:6], "b": all_snaps[4:9], "c": all_snaps[7:10]}
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes, mode="v3",
                         stream_chunk=4)  # 6 -> 4 + tail-padded chunk of 2
    params, _ = srv.init(jax.random.PRNGKey(0))
    states = {sid: srv.model.init_state(params, mode="v3") for sid in streams}
    states, outs, stats = srv.run_multi(params, states, streams)
    assert stats.phase_ms["serve.device_wait"] > 0
    assert len(stats.preprocess_ms) == sum(len(s) for s in streams.values())
    for sid, snaps in streams.items():
        off_state, off = _offline_outputs(GCRN_M2, tg, ft, params, snaps,
                                          srv.n_pad, srv.e_pad, srv.k_max)
        assert len(outs[sid]) == len(snaps)
        for t in range(len(snaps)):
            np.testing.assert_allclose(outs[sid][t], np.asarray(off)[t],
                                       atol=1e-5, err_msg=f"{sid} t={t}")
        np.testing.assert_allclose(np.asarray(states[sid]["h"]),
                                   np.asarray(off_state["h"]), atol=1e-5,
                                   err_msg=f"{sid} final state")


def test_run_multi_bucketed_same_bucket_streams_share_launch():
    """With bucketed padding, same-bucket chunks from different clients
    batch into one V3 launch while off-bucket clients run separately —
    outputs stay offline-identical on the real-node rows either way."""
    tg, ft = generate_temporal_graph(UCI)
    all_snaps = slice_snapshots(tg, 1.0)
    streams = {"a": all_snaps[:4], "b": all_snaps[2:6], "c": all_snaps[5:9]}
    buckets = ((256, 1024, 48), (640, 4096, 64))
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes, mode="v3",
                         stream_chunk=4, buckets=buckets)
    params, _ = srv.init(jax.random.PRNGKey(0))
    states = {sid: srv.model.init_state(params, mode="v3") for sid in streams}
    states, outs, _ = srv.run_multi(params, states, streams)
    model = build_model(GCRN_M2, n_global=tg.n_global_nodes)
    for sid, snaps in streams.items():
        pads = [pad_snapshot(renumber_and_normalize(s), ft, 640, 4096, 64)
                for s in snaps]
        st = model.init_state(params, mode="baseline")
        _, off = run_stream(model, params, st, stack_time(pads),
                            mode="baseline")
        for t, s in enumerate(snaps):
            nr = renumber_and_normalize(s).n_nodes
            np.testing.assert_allclose(outs[sid][t][:nr],
                                       np.asarray(off)[t][:nr], atol=1e-5,
                                       err_msg=f"{sid} t={t}")


def _split_snaps_by_bucket(snaps, buckets):
    """Partition snapshots by the bucket choose_bucket assigns them."""
    from repro.graph import choose_bucket, max_in_degree

    by_bucket = {b: [] for b in buckets}
    for s in snaps:
        ls = renumber_and_normalize(s)
        b = choose_bucket(ls.n_nodes, ls.src.shape[0], max_in_degree(ls),
                          buckets)
        by_bucket[b].append(s)
    return by_bucket


def test_promote_bucket_groups_guard_and_chain():
    """Unit contract of the grouper helper: groups merge up the chain only
    while the padded-compute guard holds against each member's ORIGINAL
    bucket, and members are re-tagged to the target bucket."""
    from repro.graph import bucket_cost, promote_bucket_groups

    buckets = ((64, 256, 8), (128, 512, 16), (640, 4096, 64))
    small, mid, big = buckets
    groups = {small: [("a", ["s"], small)], mid: [("b", ["m"], mid)]}
    # generous guard: small promotes into mid (one launch)
    merged = promote_bucket_groups(groups, buckets,
                                   bucket_cost(mid) / bucket_cost(small))
    assert set(merged) == {mid}
    assert {sid for sid, _, _ in merged[mid]} == {"a", "b"}
    assert all(b == mid for _, _, b in merged[mid])
    # tight guard: no promotion
    assert set(promote_bucket_groups(groups, buckets, 1.0)) == {small, mid}
    # chain guard: with a guard that just covers mid -> big, a lone mid
    # group promotes into big ...
    groups3 = {mid: [("b", ["m"], mid)], big: [("c", ["g"], big)]}
    ratio_mid_big = bucket_cost(big) / bucket_cost(mid)
    merged3 = promote_bucket_groups(groups3, buckets, ratio_mid_big)
    assert set(merged3) == {big}
    # ... but after absorbing a small-bucket member, the second hop is
    # guarded against that member's ORIGINAL bucket and stays put
    groups4 = {small: [("a", ["s"], small)], mid: [("b", ["m"], mid)],
               big: [("c", ["g"], big)]}
    merged4 = promote_bucket_groups(groups4, buckets, ratio_mid_big)
    assert {s for s, _, _ in merged4[mid]} == {"a", "b"}
    assert {s for s, _, _ in merged4[big]} == {"c"}


def test_run_multi_bucket_promotion_joins_inflight_batch():
    """Cross-bucket batching: two clients whose chunks land in DIFFERENT
    buckets. Without promotion each round pays two batched launches; with
    ``promote_buckets`` the smaller chunk is promoted into the larger
    bucket's in-flight launch (one launch, promoted_chunks > 0, padding
    overhead visible in ServeStats) and every client's outputs stay
    offline-identical on its real-node rows. A tight guard (1.0) keeps
    promotion off."""
    tg, ft = generate_temporal_graph(UCI)
    buckets = ((256, 1024, 48), (640, 4096, 64))
    by_bucket = _split_snaps_by_bucket(slice_snapshots(tg, 1.0), buckets)
    small, big = (by_bucket[b] for b in buckets)
    assert len(small) >= 4 and len(big) >= 4, "dataset must span buckets"
    streams = {"s": small[:4], "b": big[:4]}

    def run(promote):
        srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes,
                             mode="v3", stream_chunk=4, buckets=buckets,
                             promote_buckets=promote)
        params, _ = srv.init(jax.random.PRNGKey(0))
        states = {sid: srv.model.init_state(params, mode="v3")
                  for sid in streams}
        _, outs, stats = srv.run_multi(params, states, streams)
        return outs, stats

    outs_off, stats_off = run(None)
    assert stats_off.launches == 2 and stats_off.promoted_chunks == 0
    outs_tight, stats_tight = run(1.0)       # guard blocks promotion
    assert stats_tight.launches == 2 and stats_tight.promoted_chunks == 0
    outs_on, stats_on = run(100.0)           # generous guard: one launch
    assert stats_on.launches == 1
    assert stats_on.promoted_chunks == 1
    # the promoted chunk's padding overhead is reported, not hidden
    assert stats_on.live_snapshots == 8
    assert stats_on.padded_snapshots >= stats_off.padded_snapshots
    # outputs stay offline-identical on real-node rows, promoted or not
    model = build_model(GCRN_M2, n_global=tg.n_global_nodes)
    srv0 = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes)
    params, _ = srv0.init(jax.random.PRNGKey(0))
    for outs in (outs_on, outs_tight, outs_off):
        for sid, snaps in streams.items():
            pads = [pad_snapshot(renumber_and_normalize(s), ft, 640, 4096,
                                 64) for s in snaps]
            st = model.init_state(params, mode="baseline")
            _, off = run_stream(model, params, st, stack_time(pads),
                                mode="baseline")
            for t, s in enumerate(snaps):
                nr = renumber_and_normalize(s).n_nodes
                np.testing.assert_allclose(outs[sid][t][:nr],
                                           np.asarray(off)[t][:nr],
                                           atol=1e-5, err_msg=f"{sid} t={t}")


def test_run_multi_adaptive_promotion_guard_measured():
    """promotion_guard="measured": the server calibrates per-bucket step
    times with a tiny warmup (one timed empty-chunk launch per bucket) and
    guards promotion with the MEASURED ratio instead of the static
    n_pad*(k_max+1) proxy. Outputs stay offline-identical and a generous
    ratio still merges the two buckets into one launch."""
    from repro import api
    from repro.graph import bucket_cost, promote_bucket_groups

    tg, ft = generate_temporal_graph(UCI)
    buckets = ((256, 1024, 48), (640, 4096, 64))
    by_bucket = _split_snaps_by_bucket(slice_snapshots(tg, 1.0), buckets)
    small, big = (by_bucket[b] for b in buckets)
    streams = {"s": small[:4], "b": big[:4]}
    plan = api.plan(DGNN_CONFIGS["gcrn-m2"], level="v3", stream_chunk=4,
                    buckets=buckets, promote_buckets=1e6,
                    promotion_guard="measured")
    srv = SnapshotServer(n_global=tg.n_global_nodes, feat_table=ft,
                         session=api.BoosterSession(
                             DGNN_CONFIGS["gcrn-m2"], plan,
                             n_global=tg.n_global_nodes, feat_table=ft))
    params, _ = srv.init(jax.random.PRNGKey(0))
    states = {sid: srv.model.init_state(params, mode="v3")
              for sid in streams}
    states, outs, stats = srv.run_multi(params, states, streams)
    # calibration happened: one measured positive step time per bucket
    assert srv._bucket_ms is not None
    assert set(srv._bucket_ms) == set(buckets)
    assert all(t > 0 for t in srv._bucket_ms.values())
    # generous measured guard merged the buckets into one launch
    assert stats.launches == 1 and stats.promoted_chunks == 1
    # outputs stay offline-identical on real-node rows
    model = build_model(DGNN_CONFIGS["gcrn-m2"], n_global=tg.n_global_nodes)
    for sid, snaps in streams.items():
        pads = [pad_snapshot(renumber_and_normalize(s), ft, 640, 4096, 64)
                for s in snaps]
        st = model.init_state(params, mode="baseline")
        _, off = run_stream(model, params, st, stack_time(pads),
                            mode="baseline")
        for t, s in enumerate(snaps):
            nr = renumber_and_normalize(s).n_nodes
            np.testing.assert_allclose(outs[sid][t][:nr],
                                       np.asarray(off)[t][:nr], atol=1e-5,
                                       err_msg=f"{sid} t={t}")
    # the measured costs actually drive the guard: a ratio below the
    # measured big/small quotient blocks promotion that the static proxy
    # (or a bigger ratio) would allow
    ms = srv._bucket_ms
    ratio = ms[buckets[1]] / ms[buckets[0]]
    groups = {buckets[0]: [("s", ["x"], buckets[0])],
              buckets[1]: [("b", ["y"], buckets[1])]}
    merged = promote_bucket_groups(groups, buckets, ratio * 0.5,
                                   cost=lambda b: ms[b])
    assert set(merged) == set(buckets)  # measured guard blocks
    merged = promote_bucket_groups(groups, buckets, ratio * 2.0,
                                   cost=lambda b: ms[b])
    assert set(merged) == {buckets[1]}  # measured guard allows
    # static proxy remains the default cost
    merged = promote_bucket_groups(groups, buckets,
                                   bucket_cost(buckets[1])
                                   / bucket_cost(buckets[0]))
    assert set(merged) == {buckets[1]}


def test_run_multi_producer_exception_propagates():
    """A no-fit snapshot in ONE tenant's stream must raise out of
    run_multi (not hang the round loop) and leave the producer threads
    joinable — the multi-tenant edition of the producer-crash regression."""
    import pytest

    tg, ft = generate_temporal_graph(UCI)
    all_snaps = slice_snapshots(tg, 1.0)
    streams = {"ok": all_snaps[:3], "bad": all_snaps[3:6]}
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes, mode="v3",
                         buckets=((8, 8, 2),))  # nothing fits
    params, _ = srv.init(jax.random.PRNGKey(0))
    states = {sid: srv.model.init_state(params, mode="v3") for sid in streams}
    with pytest.raises(ValueError, match="no bucket fits"):
        srv.run_multi(params, states, streams)


def test_run_multi_evolvegcn_takes_batched_stream_path():
    """EvolveGCN joins the multi-tenant batched V3 launch: run_multi must
    NOT take the per-snapshot round-robin path (regression: PR 2 fell
    back for the weights-evolved family). Uneven stream lengths force
    no-op tail snapshots AND a no-op padding stream in the batch — each
    client's outputs and final evolving weights must still equal its own
    offline run."""
    cfg = DGNN_CONFIGS["evolvegcn"]
    tg, ft = generate_temporal_graph(UCI)
    all_snaps = slice_snapshots(tg, 1.0)
    streams = {"x": all_snaps[:4], "y": all_snaps[1:6], "z": all_snaps[3:6]}
    srv = SnapshotServer(cfg, ft, n_global=tg.n_global_nodes, mode="v3",
                         stream_chunk=4)
    _forbid_per_step(srv)
    params, _ = srv.init(jax.random.PRNGKey(0))
    states = {sid: srv.model.init_state(params, mode="v3") for sid in streams}
    states, outs, _ = srv.run_multi(params, states, streams)
    model = build_model(cfg)
    for sid, snaps in streams.items():
        _, off = _offline_outputs(cfg, tg, ft, params, snaps)
        assert len(outs[sid]) == len(snaps)
        for t in range(len(snaps)):
            np.testing.assert_allclose(outs[sid][t], np.asarray(off)[t],
                                       atol=1e-5, err_msg=f"{sid} t={t}")
        pads = [pad_snapshot(renumber_and_normalize(s), ft, srv.n_pad,
                             srv.e_pad, srv.k_max) for s in snaps]
        st1 = model.init_state(params, mode="v1")
        off_state, _ = run_stream(model, params, st1, stack_time(pads),
                                  mode="v1")
        for i, (got, want) in enumerate(zip(states[sid]["weights"],
                                            off_state["weights"])):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5,
                err_msg=f"{sid} weights[{i}] disturbed by co-tenants or "
                        "no-op padding")


def _serve_replay(streams, tg, ft, **plan_kw):
    """GCRN-M2 v3 over ``streams`` with fresh states, ``stream_chunk`` 8:
    ``(states, outs, stats)``."""
    srv = SnapshotServer(GCRN_M2, ft, n_global=tg.n_global_nodes, mode="v3",
                         stream_chunk=8, **plan_kw)
    params, _ = srv.init(jax.random.PRNGKey(0))
    states = {sid: srv.model.init_state(params, mode="v3") for sid in streams}
    return srv.run_multi(params, states, streams)


def _assert_same_serve(got, want):
    """Outputs and final states of two serve runs are equal bit for bit."""
    (g_states, g_outs, _), (w_states, w_outs, _) = got, want
    assert g_outs.keys() == w_outs.keys()
    for sid in w_outs:
        assert len(g_outs[sid]) == len(w_outs[sid])
        for t, (g, w) in enumerate(zip(g_outs[sid], w_outs[sid])):
            np.testing.assert_array_equal(g, w, err_msg=f"{sid} t={t}")
        for g, w in zip(jax.tree.leaves(g_states[sid]),
                        jax.tree.leaves(w_states[sid])):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"{sid} final state")


def test_replay_chunks_stage_in_place_and_match_copy_staging():
    """Replay-shaped traffic (4 tenants x 16 snapshots, chunks of 8, the
    rounds scheduler) with one fixed bucket: every chunk is consecutive
    rows of its producer's slab, so every chunk stages as a view. Outputs
    and final states equal, bit for bit, the same streams served through
    a one-bucket ``buckets`` plan (padded on the loop thread and stacked
    by copy) and through the continuous scheduler."""
    tg, ft = generate_temporal_graph(UCI)
    snaps = slice_snapshots(tg, 1.0)
    streams = {f"t{i}": snaps[4 * i:4 * i + 16] for i in range(4)}
    slabs = _serve_replay(streams, tg, ft)
    stats = slabs[2]
    assert (stats.staged_chunks, stats.staged_in_place) == (8, 8)
    assert stats.stage_in_place_pct == 100.0
    copied = _serve_replay(streams, tg, ft, buckets=((640, 4096, 64),))
    assert copied[2].staged_chunks == 8
    assert copied[2].stage_in_place_pct == 0.0
    _assert_same_serve(slabs, copied)
    _assert_same_serve(_serve_replay(streams, tg, ft, scheduler="continuous"),
                       slabs)


def test_ragged_tail_chunk_stages_by_copy():
    """A 13-snapshot tenant's last chunk (5 live, padded to T=8 by
    repeating its last row) is not a run of slab rows: it stages by copy,
    so the in-place share falls below 100% while the other chunks stay
    views."""
    tg, ft = generate_temporal_graph(UCI)
    snaps = slice_snapshots(tg, 1.0)
    streams = {f"t{i}": snaps[4 * i:4 * i + 16] for i in range(3)}
    streams["t3"] = snaps[12:25]
    _, outs, stats = _serve_replay(streams, tg, ft)
    assert len(outs["t3"]) == 13
    assert (stats.staged_chunks, stats.staged_in_place) == (8, 7)
    assert stats.stage_in_place_pct == 87.5


def test_lm_generate_greedy_deterministic():
    import jax.numpy as jnp

    from repro.configs import ARCHS, reduce_for_smoke
    from repro.models import RuntimeConfig, init_params
    from repro.serve import generate

    cfg = reduce_for_smoke(ARCHS["granite-moe-3b-a800m"])
    rt = RuntimeConfig(tp=1, moe_impl="dense", attn_chunk=64)
    params, _ = init_params(cfg, rt, jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    out1 = generate(params, cfg, rt, prompt, steps=5, skv=32)
    out2 = generate(params, cfg, rt, prompt, steps=5, skv=32)
    assert out1.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert (np.asarray(out1) < cfg.vocab_size).all()  # padding never sampled
