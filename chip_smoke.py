"""Chip smoke test: the DGNN-Booster serve path, end to end, on a TPU.

Drives the main path once through the entry points a user calls —
``plan(cfg, level="v3")`` -> ``BoosterSession`` -> ``serve_multi`` (the
multi-tenant ``SnapshotServer``), with the time-fused Pallas stream engine
underneath — for both of the paper's models at their published widths
(GCRN-M2 and EvolveGCN-O: in_dim 64, hidden 128, 2 GCN layers, edge_dim
8; plan defaults n_pad 640, e_pad 4096, k_max 64) on the seeded
BC-Alpha-shaped snapshot stream (578 x 6 global nodes). Weights are random
from ``--seed``. Four tenants of 16-32 snapshots each are served, and every
served output is checked against the plain XLA baseline
(``plan(cfg, level="baseline")``) on the same streams and weights, both
served at full f32 matmul precision: TPU XLA's default f32 matmul rounds
its operands through bf16, which alone moves outputs by ~1e-3 and would
hide a kernel error of that size.

    python chip_smoke.py              # one chip: both models, served
    python chip_smoke.py --chips 4    # only the batch-sharded launch on
                                      # 4 chips vs the same batch on one

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else goes on earlier lines. Any failed check raises, so the
exit status is non-zero and that line is never printed. The script needs
a TPU: on any other backend it exits non-zero before running a phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# the TPU runtime otherwise writes its logs to a fixed directory shared by
# every process on the machine
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import BoosterSession, plan  # noqa: E402
from repro.configs.dgnn import BC_ALPHA, DGNN_CONFIGS  # noqa: E402
from repro.core import stack_time  # noqa: E402
from repro.graph import (  # noqa: E402
    generate_temporal_graph,
    pad_snapshot,
    renumber_and_normalize,
    slice_snapshots,
)
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import DeviceSpec  # noqa: E402
from repro.serve import SnapshotServer  # noqa: E402

MODELS = ("gcrn-m2", "evolvegcn")
TENANT_LENGTHS = (32, 27, 21, 16)     # snapshots per tenant (ragged tails)
#: served outputs may differ from the baseline by at most this fraction of
#: max(1, max |baseline|) when both are served at full f32 matmul
#: precision: what remains is summation order and the transcendental
#: approximations of two compilers (XLA vs Mosaic), compounded over up to
#: 32 recurrent steps (at most 5.5e-7 on a v5e). A matmul rounded through
#: bf16 anywhere on either side moves outputs by ~1e-3 and fails.
TOL = 1e-5


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_label() -> str:
    d = jax.devices()
    return f"[{d[0].platform} {d[0].device_kind} x{len(d)}]"


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite output")
    return float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))


def tenant_streams(snaps: list, seed: int) -> dict:
    """Four tenants, each a contiguous run of the snapshot stream."""
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, len(snaps) - max(TENANT_LENGTHS) + 1,
                          size=len(TENANT_LENGTHS))
    return {f"tenant{i}": snaps[s:s + n]
            for i, (s, n) in enumerate(zip(starts, TENANT_LENGTHS))}


def max_err(outs: dict, want: dict) -> float:
    return max(rel_err(g, w) for sid in want
               for g, w in zip(outs[sid], want[sid]))


def serve_phase(cfg, tg, feat, streams: dict, seed: int) -> None:
    """Serve the tenants through the v3 stream engine as users run it:
    ``serve_multi`` twice (cold, then repeated: each call builds a new
    server, so it retraces its launches), then twice through one kept
    ``SnapshotServer`` (steady: no retrace). The steady server's own
    batched step, lowered with the arguments of its first launch, must
    hold the Pallas kernel. Last, serve through v3 and through the XLA
    baseline at full matmul precision and compare every output."""
    session = BoosterSession(cfg, plan(cfg, level="v3", stream_chunk=8),
                             n_global=tg.n_global_nodes, feat_table=feat,
                             rng=jax.random.PRNGKey(seed))
    n_snaps = sum(len(v) for v in streams.values())
    t0 = time.perf_counter()
    _, outs, cold_stats = session.serve_multi(streams)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, outs, repeat_stats = session.serve_multi(streams)
    repeat_ms = (time.perf_counter() - t0) * 1e3 / n_snaps

    server = SnapshotServer(session=session)
    step, first_launch = server._stream_step_batched, []

    def recorded_step(*args):
        if not first_launch:
            first_launch.extend(args)
        return step(*args)

    def fresh():
        return {sid: session.model.init_state(session.params,
                                              mode=session.plan.level)
                for sid in streams}

    server._stream_step_batched = recorded_step
    server.run_multi(session.params, fresh(), streams)
    t0 = time.perf_counter()
    _, outs, stats = server.run_multi(session.params, fresh(), streams)
    steady_ms = (time.perf_counter() - t0) * 1e3 / n_snaps
    kernel = ("tpu_custom_call"
              in step.lower(*first_launch).compile().as_text())
    for st in (cold_stats, repeat_stats, stats):
        check(st.launches > 0, f"{cfg.name}: no stream-engine launch")
        check(st.degraded_launches == 0,
              f"{cfg.name}: {st.degraded_launches} degraded launches")
        check(not st.tenant_errors,
              f"{cfg.name}: quarantined tenants {st.tenant_errors}")
        check(st.calibration_fallback is None,
              f"{cfg.name}: calibration fallback {st.calibration_fallback}")
    for sid, snaps in streams.items():
        check(len(outs[sid]) == len(snaps),
              f"{cfg.name}/{sid}: served {len(outs[sid])} of {len(snaps)}")

    base = BoosterSession(cfg, plan(cfg, level="baseline"),
                          n_global=tg.n_global_nodes, feat_table=feat,
                          params=session.params)
    with jax.default_matmul_precision("highest"):
        _, want, _ = base.serve_multi(streams)
        _, exact, _ = session.serve_multi(streams)
    err = max_err(exact, want)
    print(f"{device_label()} {cfg.name} v3 serve_multi: {len(streams)} "
          f"tenants, {n_snaps} snapshots, {stats.launches} launches, "
          f"degraded {stats.degraded_launches}; cold {cold_s:.2f} s "
          f"(compile + run); repeated serve_multi {repeat_ms:.3f} "
          f"ms/snapshot (retraces); steady {steady_ms:.3f} ms/snapshot "
          f"(staging {stats.stage_ms_per_snapshot:.3f}, "
          f"{stats.stage_in_place_pct:.0f}% of chunks in place, device wait "
          f"{stats.device_wait_ms_per_snapshot:.3f} ms/snapshot); max error "
          f"vs baseline {err:.3e} (tol {TOL:g} x max(1, |baseline|); "
          f"{max_err(outs, want):.3e} at default matmul precision); "
          f"tpu_custom_call {'present' if kernel else 'MISSING'}",
          flush=True)
    check(err <= TOL, f"{cfg.name}: error {err:.3e} over tolerance {TOL:g}")
    check(kernel, f"{cfg.name}: compiled launch has no tpu_custom_call")


def sharded_phase(cfg, tg, feat, snaps: list, seed: int, chips: int) -> None:
    """BoosterSession.run_batched of one ragged batch, its B axis sharded
    over ``chips`` devices, against the same batch on one device."""
    batch = 2 * chips
    p1 = plan(cfg, level="v3", batch=batch)
    pad = [pad_snapshot(renumber_and_normalize(s), feat, p1.n_pad,
                        p1.e_pad, p1.k_max) for s in snaps[:8 + batch]]
    streams = [stack_time(pad[b:b + 8 - b % 3]) for b in range(batch)]
    one = BoosterSession(cfg, p1, n_global=tg.n_global_nodes,
                         feat_table=feat, rng=jax.random.PRNGKey(seed))
    many = BoosterSession(cfg, plan(cfg, level="v3", batch=batch,
                                    device=DeviceSpec(chips)),
                          n_global=tg.n_global_nodes, feat_table=feat,
                          params=one.params)
    t0 = time.perf_counter()
    st1, o1 = one.run_batched(streams)
    t1 = time.perf_counter()
    stn, on = many.run_batched(streams)
    t2 = time.perf_counter()
    errs = [rel_err(a, b) for a, b in zip(on, o1)]
    errs += [rel_err(a, b) for a, b in zip(jax.tree.leaves(stn),
                                           jax.tree.leaves(st1))]
    exact = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
                zip(list(on) + jax.tree.leaves(stn),
                    list(o1) + jax.tree.leaves(st1)))
    print(f"{device_label()} {cfg.name} v3 run_batched B={batch} "
          f"(lengths {[s.neigh_idx.shape[0] for s in streams]}): sharded "
          f"over {chips} chips vs one chip, max difference {max(errs):.3e} "
          f"(bit-identical: {exact}); one chip {t1 - t0:.2f} s, {chips} "
          f"chips {t2 - t1:.2f} s (compile + run)", flush=True)
    check(max(errs) <= TOL, f"{cfg.name}: sharded launch diverged "
                            f"({max(errs):.3e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the batch-sharded 4-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devs)} device(s)")
    print(f"{device_label()} jax {jax.__version__}; compile cache {cache}",
          flush=True)

    tg, feat = generate_temporal_graph(BC_ALPHA)
    snaps = slice_snapshots(tg, 1.0)
    for name in MODELS:
        cfg = DGNN_CONFIGS[name]
        if args.chips == 1:
            serve_phase(cfg, tg, feat, tenant_streams(snaps, args.seed),
                        args.seed)
        else:
            sharded_phase(cfg, tg, feat, snaps, args.seed, args.chips)

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
