"""Kernel microbenchmarks: XLA reference path timings on CPU + the Pallas
kernels' VMEM working-set accounting (the TPU-relevant structural number).

Stream rows execute through typed StreamPlans (repro.api.run_arrays) and
record their plan fields in BENCH_streams.json (``python -m
benchmarks.kernel_bench`` merges the ledger) so the perf trajectory is
machine-trackable across PRs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.kernels import ref

from benchmarks.common import load_stream, time_step_fn, write_stream_bench
from repro.configs.dgnn import BC_ALPHA, DGNNConfig

# row name -> StreamPlan.as_dict() for rows executed through the plan API
# (written into BENCH_streams.json alongside the measurements)
PLANS: dict = {}


def _planned(name: str, plan: api.StreamPlan) -> str:
    PLANS[name] = plan.as_dict()
    return name


def vmem_bytes_spmm(n=640, k=64, d=128, tn=128) -> int:
    """Per-grid-step VMEM bytes for the ELL SpMM BlockSpec tiling."""
    x_resident = n * d * 4
    idx_tile = tn * k * 4 * 2  # idx + eidx
    coef_tile = tn * k * 4
    out_tile = tn * d * 4
    return x_resident + idx_tile + coef_tile + out_tile


def live_padded_counts(node_mask) -> tuple[int, int]:
    """Padded-vs-live snapshot slots of a (batched) stream launch.

    A snapshot slot (b, t) is LIVE when any node is masked in; everything
    else is padding (no-op T tails, no-op batch rows, promoted-bucket
    inflation). Batched rows report both so padding overhead is visible
    instead of hiding in throughput.
    """
    m = np.asarray(node_mask)
    live = int((m.sum(axis=-1) > 0).sum())
    total = int(np.prod(m.shape[:-1]))
    return live, total - live


def vmem_state_block_bytes(n_global: int, hidden: int,
                           td: int | None = None) -> int:
    """Bytes of ONE (n_global, td) state window under D-axis blocking.

    td=None is the fully resident layout ((n_global, hidden) per buffer).
    The window is the PAGING UNIT of ``state_residency="hbm_paged"``:
    each DMA ring slot stages exactly one such window from the
    HBM-resident store (``run_paged_depth_sweep`` sweeps the ring depth),
    so under paging VMEM holds only ``O(depth)`` windows instead of the
    full store.
    """
    return n_global * (hidden if td is None else td) * 4


def recurrent_state_hbm_bytes(T: int, n_global: int, hidden: int,
                              n_states: int = 2, *, time_fused: bool) -> int:
    """HBM bytes moved for the recurrent state stores over one stream.

    Per-step engines (baseline..V2) gather the (n_global, hidden) h store —
    and c for GCRN (``n_states=2``) — out of HBM and scatter it back EVERY
    snapshot: 2*T transfers per state. The time-fused V3 kernel keeps the
    stores in VMEM scratch, so each crosses HBM exactly twice per stream
    (initial load + final drain): a T× reduction, the paper's BRAM win.
    """
    per_transfer = n_global * hidden * 4
    transfers = 2 * n_states if time_fused else 2 * n_states * T
    return transfers * per_transfer


def evolving_weights_hbm_bytes(T: int, dims, *, time_fused: bool) -> int:
    """HBM bytes moved for EvolveGCN's evolving weight matrices per stream.

    Per-step engines (baseline/o1/v1) round-trip every layer's W_l^t
    through HBM each snapshot (the per-step weight-update bottleneck of
    arXiv:2210.03900): 2T transfers per stream. The weights-resident V3
    kernel keeps the W_l in VMEM scratch with the matrix-GRU evolution
    in-kernel, so each crosses HBM exactly twice (primed load + evolved
    drain): the same T× reduction the node-state kernels get.
    """
    per_transfer = sum(di * do * 4 for di, do in dims)
    transfers = 2 if time_fused else 2 * T
    return transfers * per_transfer


def run() -> list[tuple[str, float, str]]:
    rows = []
    tg, ft, snaps, sT = load_stream(BC_ALPHA, limit=2)
    ps = jax.tree.map(lambda a: a[0], sT)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(640, 128)), jnp.float32)
    f = jax.jit(lambda *a: ref.ell_spmm(*a))
    t = time_step_fn(f, ps.neigh_idx, ps.neigh_coef, ps.neigh_eidx, x)
    rows.append(("kernel/ell_spmm_xla_ref", t * 1e3,
                 f"vmem_bytes={vmem_bytes_spmm()} (fits 128KiB*... v5e VMEM 128MB)"))
    wx = jnp.asarray(np.random.default_rng(1).normal(size=(128, 384)), jnp.float32)
    wh = jnp.asarray(np.random.default_rng(2).normal(size=(128, 384)), jnp.float32)
    b = jnp.zeros((384,))
    h = x
    f2 = jax.jit(lambda *a: ref.fused_gru(*a))
    t2 = time_step_fn(f2, x, h, wx, wh, b)
    rows.append(("kernel/fused_gru_xla_ref", t2 * 1e3, "gates=3-in-1 matmul"))
    rows.extend(run_stream_vs_per_step())
    rows.extend(run_paged_depth_sweep())
    rows.extend(run_evolve_stream_vs_per_step())
    rows.extend(run_batched_streams())
    rows.extend(run_evolve_batched_streams())
    rows.extend(run_serve_schedulers())
    return rows


def _gcrn_stream_fixture(t_steps: int, hidden: int):
    """Shared GCRN bench case: the bc-alpha stream plus random gate
    weights and zero h/c stores (reused by the per-step-vs-V3 rows and
    the hbm_paged ring-depth sweep so their timings are comparable)."""
    tg, ft, snaps, sT = load_stream(BC_ALPHA, limit=t_steps)
    G = tg.n_global_nodes
    rngs = np.random.default_rng(3)
    din = sT.node_feat.shape[2]
    wx = jnp.asarray(rngs.normal(size=(din, 4 * hidden)) * 0.1, jnp.float32)
    wh = jnp.asarray(rngs.normal(size=(hidden, 4 * hidden)) * 0.1, jnp.float32)
    b = jnp.zeros((4 * hidden,), jnp.float32)
    h0 = jnp.zeros((G, hidden), jnp.float32)
    c0 = jnp.zeros((G, hidden), jnp.float32)
    return sT, G, wx, wh, b, h0, c0


def run_stream_vs_per_step(t_steps: int = 8, hidden: int = 128
                           ) -> list[tuple[str, float, str]]:
    """Per-step V2 vs time-fused V3 on the same GCRN stream.

    Kernel-level apples-to-apples: the V2 row re-invokes the fused step
    kernel from a scan with the h/c stores gathered/scattered per snapshot
    (the HBM round-trip); the V3 row is ONE stream-kernel launch with the
    stores VMEM-resident. Wall time is CPU-bound here; the structural
    number is the recurrent-state HBM estimate (T× reduction on TPU).
    """
    from repro.kernels import ops

    plan_res = api.plan(family="gcrn", level="v3")
    plan_blk = api.plan(family="gcrn", level="v3", td=hidden // 2)
    sT, G, wx, wh, b, h0, c0 = _gcrn_stream_fixture(t_steps, hidden)

    def v2_scan(h_store, c_store):
        def body(carry, s):
            hs, cs = carry
            safe = jnp.where(s["ren"] >= 0, s["ren"], 0)
            m = s["mask"][:, None]
            h = hs[safe] * m
            c = cs[safe] * m
            h_new, c_new = ops.dgnn_fused_step(
                s["idx"], s["coef"], s["eidx"], s["x"], h, c, wx, wh, b)
            h_new, c_new = h_new * m, c_new * m
            sidx = jnp.where(s["ren"] >= 0, s["ren"], hs.shape[0])
            return (hs.at[sidx].set(h_new, mode="drop"),
                    cs.at[sidx].set(c_new, mode="drop")), h_new

        xs = dict(idx=sT.neigh_idx, coef=sT.neigh_coef, eidx=sT.neigh_eidx,
                  x=sT.node_feat, ren=sT.renumber, mask=sT.node_mask)
        (hs, cs), outs = jax.lax.scan(body, (h_store, c_store), xs)
        return outs, hs, cs

    def v3_stream(h_store, c_store, plan=plan_res):
        return api.run_arrays(
            plan, sT.neigh_idx, sT.neigh_coef, sT.neigh_eidx, sT.node_feat,
            sT.renumber, sT.node_mask, h_store, c_store, wx, wh, b)

    rows = []
    bytes_v2 = recurrent_state_hbm_bytes(t_steps, G, hidden, time_fused=False)
    bytes_v3 = recurrent_state_hbm_bytes(t_steps, G, hidden, time_fused=True)
    live, padded = live_padded_counts(sT.node_mask)
    t_v2 = time_step_fn(jax.jit(v2_scan), h0, c0, iters=5)
    rows.append((f"kernel/gcrn_per_step_v2_T{t_steps}", t_v2 * 1e3,
                 f"state_hbm_bytes={bytes_v2} (h+c in/out every step)"))
    t_v3 = time_step_fn(jax.jit(v3_stream), h0, c0, iters=5)
    rows.append((_planned(f"kernel/gcrn_time_fused_v3_T{t_steps}", plan_res),
                 t_v3 * 1e3,
                 f"state_hbm_bytes={bytes_v3},"
                 f"state_hbm_reduction={bytes_v2 // bytes_v3}x,"
                 f"snaps_live={live},snaps_padded={padded}"))
    # D-blocked layout: same stream, state addressed through (G, td)
    # column windows — the VMEM-oversized-store configuration. Identical
    # outputs (the engine's round-trip contract). The window is the
    # paging unit state_residency="hbm_paged" DMA-stages per ring slot
    # (run_paged_depth_sweep); resident, all windows share one VMEM
    # scratch allocation.
    td = hidden // 2
    t_v3b = time_step_fn(jax.jit(lambda hh, cc: v3_stream(hh, cc,
                                                          plan=plan_blk)),
                         h0, c0, iters=5)
    rows.append((_planned(f"kernel/gcrn_v3_dblocked_td{td}_T{t_steps}",
                          plan_blk), t_v3b * 1e3,
                 f"state_hbm_bytes={bytes_v3},"
                 f"dblock_paging_window_bytes={vmem_state_block_bytes(G, hidden, td)},"
                 f"resident_state_bytes={vmem_state_block_bytes(G, hidden)},"
                 f"snaps_live={live},snaps_padded={padded}"))
    return rows


def run_paged_depth_sweep(t_steps: int = 8, hidden: int = 128,
                          iters: int = 3) -> list[tuple[str, float, str]]:
    """HBM-paged residency × DMA ring depth (1 / 2 / 4) on the same GCRN
    stream as ``run_stream_vs_per_step``, bit-identical outputs by the
    paging contract (tests/test_paged.py).

    depth 1 is the synchronous baseline (each window's copy blocks
    compute), 2 double-buffers (window d+1 stages while d computes), 4
    quad-buffers. CPU wall time measures the interpreter, not DMA
    overlap; the structural numbers are per-window DMA bytes (the ring
    slot's staging transfer), windows per step, ring VMEM footprint, and
    the resident store bytes paging evicts from VMEM.
    """
    td = hidden // 2
    sT, G, wx, wh, b, h0, c0 = _gcrn_stream_fixture(t_steps, hidden)
    window = vmem_state_block_bytes(G, hidden, td)
    n_win = -(-hidden // td)
    rows = []
    for depth in (1, 2, 4):
        plan = api.plan(family="gcrn", level="v3", td=td,
                        state_residency="hbm_paged", buffer_depth=depth)
        fn = jax.jit(lambda hh, cc, p=plan: api.run_arrays(
            p, sT.neigh_idx, sT.neigh_coef, sT.neigh_eidx, sT.node_feat,
            sT.renumber, sT.node_mask, hh, cc, wx, wh, b))
        t = time_step_fn(fn, h0, c0, iters=iters)
        rows.append((
            _planned(f"kernel/gcrn_v3_hbm_paged_d{depth}_td{td}_T{t_steps}",
                     plan), t * 1e3,
            f"dma_window_bytes={window},"
            f"windows_per_step={n_win},"
            f"ring_vmem_bytes={depth * window},"
            f"staging_vmem_bytes={2 * window},"
            f"resident_store_bytes_evicted="
            f"{3 * vmem_state_block_bytes(G, hidden)}"))
    return rows


def _random_evolve_stream(rngs, t_steps: int, n: int, k: int, din: int):
    """Random padded ELL stream (all-live) for the EvolveGCN kernel rows."""
    idx = rngs.integers(0, n, (t_steps, n, k)).astype(np.int32)
    coef = (rngs.uniform(size=(t_steps, n, k)) *
            (rngs.uniform(size=(t_steps, n, k)) > 0.4)).astype(np.float32)
    x = rngs.normal(size=(t_steps, n, din)).astype(np.float32)
    mask = np.ones((t_steps, n), np.float32)
    live = np.ones(t_steps, np.int32)
    return idx, coef, x, mask, live


def _evolve_params(rngs, dims):
    ws = [jnp.asarray(rngs.normal(size=d) * 0.1, jnp.float32) for d in dims]
    bg = [jnp.zeros((d[1],), jnp.float32) for d in dims]
    gwx = [jnp.asarray(rngs.normal(size=(d[0], 3 * d[0])) * 0.1, jnp.float32)
           for d in dims]
    gwh = [jnp.asarray(rngs.normal(size=(d[0], 3 * d[0])) * 0.1, jnp.float32)
           for d in dims]
    gb = [jnp.zeros((3 * d[0],), jnp.float32) for d in dims]
    return ws, bg, gwx, gwh, gb


def run_evolve_stream_vs_per_step(t_steps: int = 8, n: int = 640,
                                  k: int = 32, din: int = 64,
                                  hidden: int = 128, out: int = 64
                                  ) -> list[tuple[str, float, str]]:
    """Per-step v1 schedule vs weights-resident V3 on the same EvolveGCN
    stream.

    The per-step row scans the overlapped v1 schedule (GCN + matrix-GRU
    per snapshot) with the evolving weights re-entering the device every
    step; the V3 row is ONE stream-kernel launch with the W_l
    VMEM-resident and the evolution in-kernel. On CPU BOTH rows route to
    the XLA oracle (set_force_ref) so neither measures the Pallas
    interpreter; wall times then mostly coincide and the structural
    number — the evolving-weights HBM estimate, a T× reduction on TPU —
    is the signal, the family's edition of the paper's BRAM win.
    """
    from repro.kernels import ops

    dims = [(din, hidden), (hidden, out)]
    plan_v3 = api.plan(family="evolve", level="v3")
    rngs = np.random.default_rng(5)
    stream = _random_evolve_stream(rngs, t_steps, n, k, din)
    ws, bg, gwx, gwh, gb = _evolve_params(rngs, dims)

    def per_step(weights):  # v1 schedule: weights cross HBM every step
        return ref.evolve_stream_ref(*stream, weights, bg, gwx, gwh, gb)

    def v3_stream(weights):
        return api.run_arrays(plan_v3, *stream, weights, bg, gwx, gwh, gb)

    bytes_v1 = evolving_weights_hbm_bytes(t_steps, dims, time_fused=False)
    bytes_v3 = evolving_weights_hbm_bytes(t_steps, dims, time_fused=True)
    rows = []
    on_cpu = jax.default_backend() != "tpu"
    ops.set_force_ref(on_cpu)
    try:
        # the per-step row is ALWAYS the XLA scan oracle — that IS the v1
        # schedule's dataflow (weights re-entering the device each step);
        # only the v3 row runs the Pallas kernel (on TPU).
        t_v1 = time_step_fn(jax.jit(per_step), ws, iters=5)
        rows.append((f"kernel/evolve_per_step_v1_T{t_steps}", t_v1 * 1e3,
                     f"path=xla_ref,weights_hbm_bytes={bytes_v1} "
                     "(all W_l in/out every step)"))
        t_v3 = time_step_fn(jax.jit(v3_stream), ws, iters=5)
        rows.append((_planned(f"kernel/evolve_weights_resident_v3_T{t_steps}",
                              plan_v3), t_v3 * 1e3,
                     f"path={'xla_ref' if on_cpu else 'pallas'},"
                     f"weights_hbm_bytes={bytes_v3},"
                     f"weights_hbm_reduction={bytes_v1 // bytes_v3}x"))
    finally:
        ops.set_force_ref(False)
    return rows


def _time_batched_vs_sequential(one, bat, singles, iters: int):
    """Shared scaffold for the 1-batched-dispatch-vs-B-sequential rows:
    warm/compile both jitted programs, then median wall time of B
    sequential dispatches vs ONE batched dispatch. On CPU the kernel
    wrappers route to the XLA oracle for the duration (set_force_ref) —
    interpret-mode Pallas wall time would measure the interpreter, not
    the dataflow. Returns (t_seq_ms, t_batched_ms, path)."""
    import time as _time

    from repro.kernels import ops

    on_cpu = jax.default_backend() != "tpu"
    ops.set_force_ref(on_cpu)
    try:
        for s in singles:  # warmup/compile
            jax.block_until_ready(one(*s))
        jax.block_until_ready(bat())
        ts, tb = [], []
        for _ in range(iters):
            t0 = _time.perf_counter()
            outs = [one(*s) for s in singles]
            jax.block_until_ready(outs)
            ts.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            jax.block_until_ready(bat())
            tb.append(_time.perf_counter() - t0)
    finally:
        ops.set_force_ref(False)
    return (float(np.median(ts)) * 1e3, float(np.median(tb)) * 1e3,
            "xla_ref" if on_cpu else "pallas")


def _dispatch_rows(family: str, B: int, t_steps: int, t_seq: float,
                   t_bat: float, path: str, node_mask=None, plan=None
                   ) -> list[tuple[str, float, str]]:
    total_snaps = B * t_steps
    live, padded = (live_padded_counts(node_mask) if node_mask is not None
                    else (total_snaps, 0))
    batched_name = f"kernel/{family}_v3_batched_B{B}_T{t_steps}"
    if plan is not None:
        batched_name = _planned(batched_name, plan)
    return [
        (f"kernel/{family}_v3_sequential_B{B}_T{t_steps}", t_seq * 1e3,
         f"dispatches={B},path={path},"
         f"throughput={total_snaps / (t_seq / 1e3):.0f}_snap/s"),
        (batched_name, t_bat * 1e3,
         f"dispatches=1,path={path},"
         f"throughput={total_snaps / (t_bat / 1e3):.0f}_snap/s,"
         f"snaps_live={live},snaps_padded={padded},"
         f"speedup_vs_sequential={t_seq / t_bat:.2f}x"),
    ]


def run_evolve_batched_streams(B: int = 8, t_steps: int = 4, n: int = 64,
                               k: int = 8, din: int = 16, hidden: int = 32,
                               out: int = 16, iters: int = 11
                               ) -> list[tuple[str, float, str]]:
    """Batched weights-resident V3 (ONE dispatch, B EvolveGCN streams)
    vs B separate single-stream dispatches — the multi-tenant win for the
    weights-evolved family, in the same small-snapshot regime as the
    GCRN rows. Streams carry DISTINCT evolving weights (each tenant's
    recurrent state) and distinct inputs; GRU params are shared and
    loaded once per launch. The structural numbers (dispatches B -> 1,
    weight-state transfers 2/stream) carry to TPU.
    """
    dims = [(din, hidden), (hidden, out)]
    rngs = np.random.default_rng(6)
    streams = [_random_evolve_stream(rngs, t_steps, n, k, din)
               for _ in range(B)]
    single = [tuple(jnp.asarray(a) for a in s) for s in streams]
    batch = tuple(jnp.asarray(np.stack([s[i] for s in streams]))
                  for i in range(5))
    _, bg, gwx, gwh, gb = _evolve_params(rngs, dims)
    wsB = [jnp.asarray(rngs.normal(size=(B,) + d) * 0.1, jnp.float32)
           for d in dims]

    p1 = api.plan(family="evolve", level="v3")
    pB = api.plan(family="evolve", level="v3", batch=B)
    one = jax.jit(lambda s, w: api.run_arrays(p1, *s, w, bg, gwx, gwh, gb))
    bat = jax.jit(lambda w: api.run_arrays(pB, *batch, w, bg, gwx, gwh, gb))
    t_seq, t_bat, path = _time_batched_vs_sequential(
        one, lambda: bat(wsB),
        [(single[i], [w[i] for w in wsB]) for i in range(B)], iters)
    return _dispatch_rows("evolve", B, t_steps, t_seq, t_bat, path,
                          node_mask=batch[3], plan=pB)


def run_batched_streams(B: int = 8, t_steps: int = 4, n: int = 64,
                        k: int = 8, din: int = 16, hidden: int = 32,
                        n_global: int = 200, iters: int = 11
                        ) -> list[tuple[str, float, str]]:
    """Batched V3 (ONE dispatch, B streams) vs B separate V3 dispatches.

    This measures what the multi-tenant server amortizes, in the regime
    batching exists for: SMALL per-tenant snapshots whose individual
    streams underutilize the device (the low-parallelism bottleneck of
    arXiv:2210.03900). Without batching, B clients cost B device
    dispatches per chunk and B short scans; with the batch grid axis they
    cost one dispatch whose per-step work is B× wider. Streams are B
    distinct random streams (identical inputs would let XLA CSE collapse
    the sequential program and fake the comparison); the structural
    numbers (dispatches B -> 1, recurrent-state HBM transfers 2/stream
    either way) carry over to the TPU build.
    """
    rngs = np.random.default_rng(4)

    def one_stream():
        idx = rngs.integers(0, n, (t_steps, n, k)).astype(np.int32)
        coef = (rngs.uniform(size=(t_steps, n, k)) *
                (rngs.uniform(size=(t_steps, n, k)) > 0.4)).astype(np.float32)
        eidx = rngs.integers(0, 4 * n, (t_steps, n, k)).astype(np.int32)
        x = rngs.normal(size=(t_steps, n, din)).astype(np.float32)
        ren = np.stack([np.sort(rngs.permutation(n_global)[:n])
                        for _ in range(t_steps)]).astype(np.int32)
        mask = np.ones((t_steps, n), np.float32)
        return idx, coef, eidx, x, ren, mask

    streams = [one_stream() for _ in range(B)]
    single = [tuple(jnp.asarray(a) for a in s) for s in streams]
    batch = tuple(jnp.asarray(np.stack([s[i] for s in streams]))
                  for i in range(6))
    wx = jnp.asarray(rngs.normal(size=(din, 4 * hidden)) * 0.1, jnp.float32)
    wh = jnp.asarray(rngs.normal(size=(hidden, 4 * hidden)) * 0.1, jnp.float32)
    b = jnp.zeros((4 * hidden,), jnp.float32)
    h0B = jnp.asarray(rngs.normal(size=(B, n_global, hidden)) * 0.1,
                      jnp.float32)
    c0B = jnp.asarray(rngs.normal(size=(B, n_global, hidden)) * 0.1,
                      jnp.float32)

    p1 = api.plan(family="gcrn", level="v3")
    pB = api.plan(family="gcrn", level="v3", batch=B)
    one = jax.jit(lambda s, hh, cc: api.run_arrays(p1, *s, hh, cc, wx, wh, b))
    bat = jax.jit(lambda hB, cB: api.run_arrays(pB, *batch, hB, cB,
                                                wx, wh, b))
    t_seq, t_bat, path = _time_batched_vs_sequential(
        one, lambda: bat(h0B, c0B),
        [(single[i], h0B[i], c0B[i]) for i in range(B)], iters)
    return _dispatch_rows("gcrn", B, t_steps, t_seq, t_bat, path,
                          node_mask=batch[5], plan=pB)


def run_serve_schedulers(n_backlog: int = 24, n_inc_snaps: int = 6,
                         n_inc_tenants: int = 3, interval_ms: float = 50.0,
                         chunk: int = 4, repeats: int = 2
                         ) -> list[tuple[str, float, str]]:
    """Round-based vs continuous serve scheduler under a SKEWED workload:
    one tenant with a deep snapshot backlog (all available at t=0 — a
    client replaying history) plus latency-sensitive incremental tenants
    whose snapshots ARRIVE one every ``interval_ms``.

    The headline number is the incremental tenants' p99 SOJOURN latency
    (commit wall-clock minus snapshot arrival, from ``ServeStats.
    commit_ms`` and an arrival clock stamped in the stream iterators).
    The round loop gathers a full chunk from EVERY tenant behind a
    barrier before launching, so an incremental snapshot waits for its
    chunk-mates to trickle in; the continuous scheduler serves whatever
    is ready each tick and drains the backlog ``prefill_chunk`` at a time
    in the gaps. Each scheduler gets one unpaced warm-up run (jit cache)
    plus ``repeats`` paced runs, best p99 reported — launch signatures
    depend on tick composition, so a first paced run can still hit a
    stray compile.
    """
    cfg = DGNNConfig(name="bench-sched-gcrn", dgnn_type="integrated",
                     gnn="gcn", rnn="lstm", dataflow="v3", in_dim=4,
                     hidden=8, out_dim=4, n_gnn_layers=1, edge_dim=2)
    from repro.graph.coo import COOSnapshot
    from repro.serve import SnapshotServer

    n_global = 32
    rngs = np.random.default_rng(11)
    feat = np.asarray(rngs.normal(size=(n_global, 4)), np.float32)

    def make_snaps(n_snap, seed):
        r = np.random.default_rng(seed)
        out = []
        for t in range(n_snap):
            e = int(r.integers(3, 7))
            out.append(COOSnapshot(
                src=r.integers(0, n_global, size=e),
                dst=r.choice(n_global, size=e, replace=False),
                edge_feat=np.asarray(r.normal(size=(e, 2)), np.float32),
                t_index=t))
        return out

    tenant_snaps = {"backlog": make_snaps(n_backlog, 100)}
    inc_sids = [f"inc{i}" for i in range(n_inc_tenants)]
    for i, sid in enumerate(inc_sids):
        tenant_snaps[sid] = make_snaps(n_inc_snaps, 200 + i)

    def paced(sid, arrivals):
        def gen():
            for i, s in enumerate(tenant_snaps[sid]):
                time.sleep(interval_ms / 1e3)
                arrivals[(sid, i)] = time.perf_counter()
                yield s
        return gen()

    rows = []
    variants = (("rounds", {}),
                ("continuous", dict(scheduler="continuous",
                                    state_pool_pages=n_inc_tenants + 1,
                                    prefill_chunk=2)))
    for sched, kw in variants:
        # pads sized to the tiny synthetic graphs: launch cost must sit
        # well under the arrival interval, the regime continuous batching
        # exists for (the default 640-node pads would make every launch
        # slower than the arrivals and the device the only bottleneck)
        plan = api.plan(cfg, level="v3", stream_chunk=chunk, queue_depth=64,
                        n_pad=32, e_pad=128, k_max=8, **kw)
        sess = api.BoosterSession(cfg, plan, n_global=n_global,
                                  feat_table=feat)
        srv = SnapshotServer(session=sess)
        params, _ = srv.init(jax.random.PRNGKey(0))

        # warm every (B, T) launch signature a tick could compose (tick
        # composition is timing-dependent, so an un-warmed signature would
        # charge a few hundred ms of CPU compile to whichever snapshot's
        # launch hits it first and poison the latency percentiles)
        from repro.core import stack_time
        from repro.graph.padding import stack_streams
        ps, _ = srv._prepare(tenant_snaps["backlog"][0])
        state = srv.model.init_state(params, mode=srv.mode)
        for b_sig in (1, 2, 4):
            for t_sig in (1, 2, 4):
                st_b = jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                    *([state] * b_sig))
                _, out = srv._launch_ragged(
                    params, st_b,
                    stack_streams([stack_time([ps] * t_sig)] * b_sig),
                    np.asarray([t_sig] * b_sig, np.int32))
                jax.block_until_ready(out)

        def run_once(pace):
            arrivals: dict = {}
            streams = {"backlog": list(tenant_snaps["backlog"])}
            for sid in inc_sids:
                streams[sid] = (paced(sid, arrivals) if pace
                                else list(tenant_snaps[sid]))
            states = {sid: srv.model.init_state(params, mode=srv.mode)
                      for sid in streams}
            _, outs, stats = srv.run_multi(params, states, streams)
            assert not stats.tenant_errors
            assert all(len(outs[s]) == len(tenant_snaps[s]) for s in streams)
            return arrivals, stats

        run_once(pace=False)  # warm the jit cache / launch signatures
        best = None
        for _ in range(repeats):
            arrivals, stats = run_once(pace=True)
            soj = [stats.commit_ms[sid][i]
                   - (arrivals[(sid, i)] - srv._trace.t0) * 1e3
                   for sid in inc_sids for i in range(n_inc_snaps)]
            p99 = float(np.percentile(soj, 99))
            if best is None or p99 < best[0]:
                served = sum(len(v) for v in stats.commit_ms.values())
                best = (p99, float(np.median(soj)), stats, served)
        p99, p50, stats, served = best
        thru = served / (stats.total_ms / 1e3)
        rows.append((_planned(f"serve/sched_{sched}_gcrn_skewed", plan),
                     p99 * 1e3,  # ledger unit is us_per_call
                     f"p99_ms={p99:.2f},p50_ms={p50:.2f},"
                     f"wall_ms={stats.total_ms:.0f},"
                     f"thru={thru:.0f}_snap/s,launches={stats.launches},"
                     f"ticks={stats.ticks},prefill={stats.prefill_chunks},"
                     f"evictions={stats.evictions}"))
    return rows


if __name__ == "__main__":
    rows = run()
    for r in rows:
        print(",".join(map(str, r)))
    write_stream_bench(rows, PLANS)
