import os
# CPU-only tool: 512 host-platform devices, never the accelerator (a
# process holding the chip would lock every other process off it);
# --all children inherit both settings through os.environ.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  - build the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  - resolve the runtime config (FSDP + int8 moments for the big archs,
    sequence-sharded KV for long_500k),
  - jit the right step (train / prefill / decode) against ShapeDtypeStructs
    with NamedShardings from the logical-axis rules,
  - .lower().compile() — success proves the sharding config is coherent,
  - record memory_analysis, cost_analysis, parsed collective bytes, and the
    roofline terms (with itemized trip-count corrections) to JSON.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--jobs 4]      # full sweep, subprocesses
"""
import argparse
import json
import logging
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, cell_status, get_config
from repro.distributed.api import DEFAULT_RULES, sharding_ctx, tree_shardings
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import RuntimeConfig
from repro.optim import AdamWConfig
from repro.roofline import (Roofline, collective_bytes, cost_analysis_dict,
                            model_flops)
from repro.roofline.corrections import total_corrections

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")

FSDP_THRESHOLD = 8e9        # params; above this shard weights over 'data'
INT8_THRESHOLD = 100e9      # params; above this quantize optimizer moments


def resolve_runtime(cfg, shape, overrides: dict | None = None) -> tuple[RuntimeConfig, AdamWConfig, dict]:
    n = cfg.param_count()
    fsdp = n > FSDP_THRESHOLD
    big = n > INT8_THRESHOLD
    rt = RuntimeConfig(
        tp=16,
        scan_layers=False,           # unrolled: exact per-layer accounting
        remat=True,
        attn_chunk=2048,
        moe_impl="ep",
        fsdp=fsdp,
        long_ctx=(shape.name == "long_500k"),
        loss_chunk=512,
        param_dtype="bf16" if big else "fp32",
        grad_accum=8 if big else 1,
    )
    opt = AdamWConfig(state_dtype="int8" if big else "fp32")
    rules = dict(DEFAULT_RULES)
    if not fsdp:
        rules["embed_fsdp"] = None
    if overrides:
        import dataclasses as _dc

        if overrides.get("rt"):
            rt = _dc.replace(rt, **overrides["rt"])
        rules.update(overrides.get("rules", {}))
        if overrides.get("opt"):
            opt = _dc.replace(opt, **overrides["opt"])
        if overrides.get("norm_lowmem"):
            from repro.nn.layers import set_lowmem_norm

            set_lowmem_norm(True)
        if overrides.get("ssd_bf16"):
            from repro.nn.mamba2 import set_ssd_bf16

            set_ssd_bf16(True)
    return rt, opt, rules


def _compile_once(cfg, shape, rt, opt_cfg, rules, mesh):
    """jit+lower+compile one step; returns (compiled, lower_s, compile_s)."""
    t0 = time.time()
    with sharding_ctx(mesh, rules):
        pshapes, paxes = S.abstract_params(cfg, rt)
        pshard = tree_shardings(pshapes, paxes, mesh)
        bspecs, baxes = S.batch_specs(cfg, shape)
        bshard = tree_shardings(bspecs, baxes, mesh)
        if shape.kind == "train":
            oshapes, oaxes = S.abstract_opt_state(pshapes, paxes, opt_cfg)
            oshard = tree_shardings(oshapes, oaxes, mesh)
            fn = S.make_train_step_fn(cfg, rt, opt_cfg)
            jitted = jax.jit(fn, in_shardings=(pshard, oshard, bshard),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(pshapes, oshapes, bspecs)
        elif shape.kind == "prefill":
            fn = S.make_prefill_fn(cfg, rt)
            jitted = jax.jit(fn, in_shardings=(pshard, bshard))
            lowered = jitted.lower(pshapes, bspecs)
        else:  # decode
            cshapes, caxes = S.abstract_caches(cfg, rt, shape.global_batch,
                                               shape.seq_len)
            cshard = tree_shardings(cshapes, caxes, mesh)
            fn = S.make_decode_fn(cfg, rt)
            jitted = jax.jit(fn, in_shardings=(pshard, cshard, bshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(pshapes, cshapes, bspecs)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    return compiled, t1 - t0, t2 - t1


def _measure(compiled) -> dict:
    ca = cost_analysis_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll.total_bytes),
        "coll_by_op": coll.bytes_by_op,
        "coll_counts": coll.count_by_op,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None) -> dict:
    """One dry-run cell.

    1. FULL model, scan_layers=True: lower+compile on the production mesh —
       the deliverable (sharding coherence + memory_analysis fits).
    2. (single-pod only) 1-block and 2-block UNROLLED variants compile to
       give exact per-layer-block cost/collective deltas; totals linearly
       extrapolate to n_layers blocks (XLA's HloCostAnalysis counts while
       bodies once, so the scanned compile cannot be used for cost). The
       inner chunk loops (attention/SSD/loss maps) are topped up by the
       closed-form trip-count corrections.
    All artifact numbers are PER-DEVICE (verified); roofline reports them
    per device against per-chip peaks.
    """
    import dataclasses

    cfg = get_config(arch)
    if overrides and overrides.get("cfg"):
        import dataclasses as _dc

        cfg = _dc.replace(cfg, **overrides["cfg"])
    shape = SHAPES[shape_name]
    status = cell_status(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": status,
    }
    if status != "run":
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rt, opt_cfg, rules = resolve_runtime(cfg, shape, overrides)

    # --- 1. full-model compile (the deliverable) ---
    rt_full = dataclasses.replace(rt, scan_layers=True)
    compiled, rec["lower_s"], rec["compile_s"] = _compile_once(
        cfg, shape, rt_full, opt_cfg, rules, mesh)
    mem = compiled.memory_analysis()
    if mem is not None:
        for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(mem, f, None)
            if v is not None:
                rec.setdefault("memory", {})[f] = int(v)
        ms = rec.get("memory", {})
        per_dev = ms.get("temp_size_in_bytes", 0) + ms.get("argument_size_in_bytes", 0)
        ms["per_device_bytes"] = int(per_dev)
        ms["fits_16GB"] = bool(per_dev < 16e9)
    del compiled

    if multi_pod:
        return rec  # roofline table is single-pod (per assignment)

    # --- 2. per-block extrapolation compiles ---
    period = cfg.scan_period()
    nb = cfg.n_layers // period
    rt_u = dataclasses.replace(rt, scan_layers=False)
    cfg1 = dataclasses.replace(cfg, n_layers=period)
    cfg2 = dataclasses.replace(cfg, n_layers=2 * period)
    c1, _, t_c1 = _compile_once(cfg1, shape, rt_u, opt_cfg, rules, mesh)
    m1 = _measure(c1)
    del c1
    c2, _, t_c2 = _compile_once(cfg2, shape, rt_u, opt_cfg, rules, mesh)
    m2 = _measure(c2)
    del c2
    rec["extrap_compile_s"] = t_c1 + t_c2

    # grad-accum scan body is counted once by HloCostAnalysis but runs
    # `a` times per step (each on batch/a) -> scale to the full step.
    accum = rt.grad_accum if shape.kind == "train" else 1

    def extrap(key):
        return (m1[key] + (nb - 1) * (m2[key] - m1[key])) * accum

    coll_by_op = {
        k: (m1["coll_by_op"].get(k, 0)
            + (nb - 1) * (m2["coll_by_op"].get(k, 0) - m1["coll_by_op"].get(k, 0))
            ) * accum
        for k in set(m1["coll_by_op"]) | set(m2["coll_by_op"])
    }
    corr = total_corrections(cfg, shape, rt.tp, rt.attn_chunk, rt.loss_chunk,
                             attn_impl=rt.attn_impl, flash_bq=rt.flash_bq,
                             flash_bk=rt.flash_bk)
    flops = extrap("flops") + corr["flops"] / chips
    bytes_hbm = extrap("bytes") + corr["bytes_hbm"] / chips
    rl = Roofline(
        flops=flops, bytes_hbm=bytes_hbm,
        bytes_coll=extrap("coll_bytes"), chips=chips,
        model_flops=model_flops(cfg, shape),
    )
    rec.update(
        measured={"one_block": m1, "two_block": m2, "n_blocks": nb},
        corrections=corr,
        collectives={"bytes_by_op": coll_by_op,
                     "count_by_op_2blk": m2["coll_counts"]},
        roofline=rl.to_dict(),
    )
    return rec


def cell_out_path(arch, shape_name, multi_pod) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = "mp" if multi_pod else "sp"
    return os.path.join(OUT_DIR, f"{arch}__{shape_name}__{tag}.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--overrides", default=None,
                    help="JSON runtime overrides (hillclimb experiments)")
    ap.add_argument("--tag", default=None, help="suffix for the output file")
    args = ap.parse_args()

    if args.all:
        import subprocess

        cells = []
        for arch in sorted(ARCHS):
            for sn in SHAPES:
                for mp in (False, True):
                    cells.append((arch, sn, mp))
        procs: list = []
        failures = []
        for arch, sn, mp in cells:
            out = cell_out_path(arch, sn, mp)
            if os.path.exists(out):
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", sn] + (["--multi-pod"] if mp else [])
            while len(procs) >= args.jobs:
                for p in list(procs):
                    if p[0].poll() is not None:
                        procs.remove(p)
                        if p[0].returncode != 0:
                            failures.append(p[1])
                time.sleep(2)
            procs.append((subprocess.Popen(cmd, env={**os.environ}), (arch, sn, mp)))
            print("launched", arch, sn, "mp" if mp else "sp", flush=True)
        for p, cell in procs:
            if p.wait() != 0:
                failures.append(cell)
        print("failures:", failures)
        return 1 if failures else 0

    overrides = json.loads(args.overrides) if args.overrides else None
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, overrides)
    except (ValueError, TypeError, KeyError, RuntimeError) as e:
        # the expected compile-cell failures: config resolution errors
        # (ValueError/TypeError/KeyError) and XLA lowering/compile errors
        # (XlaRuntimeError is a RuntimeError). Anything else — OOM, bad
        # interpreter state — should crash the sweep loudly.
        logging.warning("dry-run cell %s/%s failed: %s: %s",
                        args.arch, args.shape, type(e).__name__, e)
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "status": "error", "traceback": traceback.format_exc()}
    out = cell_out_path(args.arch, args.shape, args.multi_pod)
    if args.tag:
        out = out.replace(".json", f"__{args.tag}.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=2, default=str)
    print(json.dumps({k: rec.get(k) for k in
                      ("arch", "shape", "mesh", "status", "compile_s")},
                     indent=None))
    if rec["status"] == "error":
        print(rec["traceback"][-3000:])
        return 1
    if rec["status"].startswith("skip"):
        return 0
    print("roofline:", json.dumps(rec.get("roofline", {}), indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
