"""End-to-end DGNN serving driver (the paper's deployment scenario).

Runs both base models (EvolveGCN -> V1/V3, GCRN-M2 -> V2/V3) over both
datasets (BC-Alpha, UCI), with the paper's ablation levels, and prints the
Table IV / Fig. 6 style comparison measured on this host. Everything goes
through the typed plan/execute API: a validated ``StreamPlan`` per
configuration, a ``BoosterSession`` owning params/state, and the serving
engine as a consumer of the session. V3 is the time-fused stream engine:
the server batches snapshots into chunks and the recurrent state — the
node store for GCRN, the evolving weight matrices for EvolveGCN — stays
in VMEM across each chunk. Batched multi-stream serving is included
(--streams N), plus a RAGGED batch: unequal-length streams in ONE launch
via the plan's ``lengths`` capability.

    PYTHONPATH=src python examples/serve_stream.py [--snapshots 32] [--streams 4]
"""
import argparse
import time

import jax
import numpy as np

from repro.api import BoosterSession, plan
from repro.configs.dgnn import BC_ALPHA, UCI, DGNN_CONFIGS
from repro.core import init_states_batched, run_plan_batched, stack_time
from repro.graph import (
    generate_temporal_graph,
    pad_snapshot,
    renumber_and_normalize,
    slice_snapshots,
)
from repro.launch.cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--snapshots", type=int, default=24)
    ap.add_argument("--streams", type=int, default=4)
    args = ap.parse_args()
    use_compile_cache()

    pairs = [("evolvegcn", ("v1", "v3")), ("gcrn-m2", ("v2", "v3"))]
    for ds in (BC_ALPHA, UCI):
        tg, ft = generate_temporal_graph(ds)
        snaps = slice_snapshots(tg, 1.0)[: args.snapshots]
        for name, levels in pairs:
            for lv in ("baseline",) + levels:
                session = BoosterSession(
                    DGNN_CONFIGS[name], plan(DGNN_CONFIGS[name], level=lv),
                    n_global=tg.n_global_nodes, feat_table=ft,
                    rng=jax.random.PRNGKey(0))
                _, stats = session.serve(snaps)
                print(f"{ds.name:9s} {name:10s} {lv:8s} "
                      f"staging {stats.stage_ms_per_snapshot:8.3f} "
                      f"device wait {stats.device_wait_ms_per_snapshot:8.3f} "
                      f"ms/snapshot (host prep "
                      f"{np.mean(stats.preprocess_ms):.3f} ms, overlapped)")

    # batched multi-stream serving: the production throughput axis.
    # level="v3" runs ALL B streams through ONE batched stream-kernel
    # launch (batch axis = leading grid dimension, one VMEM-resident
    # state store per stream).
    ds = BC_ALPHA
    tg, ft = generate_temporal_graph(ds)
    snaps = slice_snapshots(tg, 1.0)[: args.snapshots]
    pads = [pad_snapshot(renumber_and_normalize(s), ft, 640, 4096, 64)
            for s in snaps]
    sT = stack_time(pads)
    B = args.streams
    sBT = jax.tree.map(lambda a: np.stack([a] * B, axis=0), sT)
    cfg = DGNN_CONFIGS["gcrn-m2"]
    for lv in ("v2", "v3"):
        p = plan(cfg, level=lv, batch=B)
        session = BoosterSession(cfg, p, n_global=tg.n_global_nodes,
                                 feat_table=ft, rng=jax.random.PRNGKey(0))
        states = init_states_batched(session.model, session.params, B,
                                     mode=lv)
        run = jax.jit(lambda pr, s, x, p=p: run_plan_batched(
            session.model, pr, s, x, p)[1])
        out = run(session.params, states, sBT)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = run(session.params, states, sBT)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        total = B * args.snapshots
        launches = "1 batched stream launch" if lv == "v3" else "vmapped scan"
        print(f"\nbatched streams [{lv}]: {B} x {args.snapshots} snapshots in "
              f"{dt*1e3:.1f} ms -> {total/dt:.0f} snapshots/s ({launches})")

    # RAGGED batch: unequal-length streams in ONE launch — the plan's
    # ``lengths`` capability masks each stream's dead tail in-launch, and
    # the session slices outputs back to true lengths.
    session = BoosterSession(cfg, plan(cfg, level="v3"),
                             n_global=tg.n_global_nodes, feat_table=ft,
                             rng=jax.random.PRNGKey(0))
    lens = [max(args.snapshots // (i + 1), 2) for i in range(B)]
    ragged = [stack_time(pads[:t]) for t in lens]
    _, outs = session.run_batched(ragged)
    print(f"ragged batch [v3]: lengths {lens} in one launch -> "
          f"per-stream outputs {[o.shape[0] for o in outs]}")

    # multi-tenant server: independent clients, same-bucket chunks from
    # different clients grouped into one batched V3 launch
    n_per = max(args.snapshots // 2, 2)
    streams = {f"client{i}": slice_snapshots(tg, 1.0)[i: i + n_per]
               for i in range(args.streams)}
    session = BoosterSession(cfg, plan(cfg, level="v3", stream_chunk=4),
                             n_global=tg.n_global_nodes, feat_table=ft,
                             rng=jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    _, outs, stats = session.serve_multi(streams)
    dt = time.perf_counter() - t0
    served = sum(len(v) for v in outs.values())
    print(f"multi-tenant v3: {len(streams)} clients, {served} snapshots in "
          f"{dt*1e3:.1f} ms (staging {stats.stage_ms_per_snapshot:.3f}, "
          f"{stats.stage_in_place_pct:.0f}% of chunks in place, "
          f"device wait {stats.device_wait_ms_per_snapshot:.3f} "
          f"ms/snapshot, host prep overlapped across {len(streams)} "
          f"producer threads)")


if __name__ == "__main__":
    main()
