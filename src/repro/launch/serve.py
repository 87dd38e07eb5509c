"""Serving launcher (the paper's deployment mode).

DGNN mode: stream synthetic BC-Alpha/UCI snapshots through a DGNN-Booster
engine with the host/device task split.
LM mode: batched greedy generation from a registered arch (reduced config
on this container).

  PYTHONPATH=src python -m repro.launch.serve --dgnn gcrn-m2 --dataset uci
  PYTHONPATH=src python -m repro.launch.serve --lm jamba-v0.1-52b --steps 16
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, DATASETS, DGNN_CONFIGS, list_archs, reduce_for_smoke
from repro.graph import generate_temporal_graph, slice_snapshots
from repro.models import RuntimeConfig, init_params
from repro.serve import SnapshotServer, generate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dgnn", choices=sorted(DGNN_CONFIGS), default=None)
    ap.add_argument("--dataset", choices=sorted(DATASETS), default="uci")
    ap.add_argument("--mode", default=None, help="baseline|o1|v1|v2")
    ap.add_argument("--snapshots", type=int, default=32)
    ap.add_argument("--lm", choices=list_archs(), default=None)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    args = ap.parse_args()

    if args.lm:
        cfg = reduce_for_smoke(ARCHS[args.lm])
        if not cfg.supports_decode:
            raise SystemExit(f"{args.lm} is encoder-only")
        rt = RuntimeConfig(tp=1, moe_impl="dense", attn_chunk=128)
        params, _ = init_params(cfg, rt, jax.random.PRNGKey(0))
        prompt = jnp.ones((args.batch, 4), jnp.int32)
        toks = generate(params, cfg, rt, prompt, steps=args.steps, skv=256)
        print(f"{args.lm}: generated {toks.shape} tokens")
        print(np.asarray(toks))
        return

    name = args.dgnn or "gcrn-m2"
    ds = DATASETS[args.dataset]
    tg, ft = generate_temporal_graph(ds)
    snaps = slice_snapshots(tg, 1.0)[: args.snapshots]
    srv = SnapshotServer(DGNN_CONFIGS[name], ft, n_global=tg.n_global_nodes,
                         mode=args.mode)
    params, state = srv.init(jax.random.PRNGKey(0))
    _, outs, stats = srv.run(params, state, snaps)
    print(f"{name} ({srv.mode}) on {ds.name}: {len(outs)} snapshots, "
          f"{stats.stage_ms_per_snapshot:.3f} ms/snapshot host staging "
          f"({stats.stage_in_place_pct:.0f}% of chunks stacked in place), "
          f"{stats.device_wait_ms_per_snapshot:.3f} ms device wait, "
          f"{np.mean(stats.preprocess_ms):.3f} ms host (overlapped), "
          f"{stats.total_ms:.1f} ms total")


if __name__ == "__main__":
    main()
