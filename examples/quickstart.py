"""Quickstart: serve a dynamic-graph stream through DGNN-Booster V2.

Generates a UCI-like temporal graph, slices snapshots on the host, and
streams them through the GCRN-M2 model with the V2 fused dataflow —
the paper's end-to-end inference pipeline in ~30 lines of user code.

The surface is the typed plan/execute API: build ONE validated
``StreamPlan`` (dataflow level, tiling, serve policy — anything invalid
raises right here, not at launch), bind it to a ``BoosterSession`` that
owns the params and recurrent state, and serve.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.api import BoosterSession, plan
from repro.configs.dgnn import GCRN_M2, STATIC_GCN, TGN, UCI
from repro.graph import generate_temporal_graph, pad_event_block, slice_snapshots

def main():
    # 1. data: time-stamped COO edges (here: synthetic UCI-like stream)
    tg, feat_table = generate_temporal_graph(UCI)
    snapshots = slice_snapshots(tg, time_splitter=1.0)[:24]

    # 2. plan + session: GCRN-M2 with the V2 (intra-step GNN/RNN fusion)
    #    dataflow, validated at construction time
    session = BoosterSession(GCRN_M2, plan(GCRN_M2, level="v2"),
                             n_global=tg.n_global_nodes,
                             feat_table=feat_table,
                             rng=jax.random.PRNGKey(0))

    # 3. serve: host thread preprocesses (CPU tasks), device consumes
    outputs, stats = session.serve(snapshots)

    print(f"served {len(outputs)} snapshots")
    print(f"launch host staging  : {stats.stage_ms_per_snapshot:8.3f} ms/snapshot")
    print(f"device wait          : {stats.device_wait_ms_per_snapshot:8.3f} ms/snapshot")
    print(f"mean host preprocess : {np.mean(stats.preprocess_ms):8.3f} ms/snapshot (overlapped)")
    print(f"end-to-end           : {stats.total_ms:8.1f} ms total")
    print(f"embedding of node 0 @ last snapshot: {outputs[-1][0, :4]}")

    # 4. the other two temporal contracts through the SAME engine
    #    (docs/stream_engine.md): a static GCN — no recurrence, snapshots
    #    fold onto the batch axis — and an event-driven TGN whose global
    #    node memory stays on-chip across ragged event batches.
    static = BoosterSession(STATIC_GCN, plan(STATIC_GCN),
                            n_global=tg.n_global_nodes,
                            feat_table=feat_table,
                            rng=jax.random.PRNGKey(1))
    s_outs, s_stats = static.serve(snapshots[:8])
    print(f"static_gcn (temporal={static.plan.temporal!r}): "
          f"served {len(s_outs)} independent snapshots, "
          f"{s_stats.stage_ms_per_snapshot:.3f} ms/snapshot staging, "
          f"{s_stats.device_wait_ms_per_snapshot:.3f} ms/snapshot device "
          f"wait")

    rng = np.random.default_rng(7)
    G = tg.n_global_nodes
    blocks = []
    for _ in range(4):  # 4 batches of 12 timestamped interactions
        src = rng.integers(0, G, 12)
        dst = (src + rng.integers(1, G, 12)) % G
        ts = rng.uniform(0.0, 10.0, 12).astype(np.float32)
        blocks.append(pad_event_block(src, dst, ts, feat_table,
                                      n_pad=32, k_max=8))
    tgn = BoosterSession(TGN, plan(TGN, level="v3"), n_global=G,
                         feat_table=feat_table,
                         rng=jax.random.PRNGKey(2))
    t_outs = tgn.run(jax.tree.map(lambda *xs: np.stack(xs), *blocks))
    print(f"tgn (temporal={tgn.plan.temporal!r}): "
          f"{len(blocks)} event batches -> outputs {np.asarray(t_outs).shape}, "
          f"memory store ({G}, {TGN.hidden}) resident across batches")


if __name__ == "__main__":
    main()
