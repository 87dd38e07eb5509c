"""Chip benchmark of the DGNN-Booster serve path: one cell of
``BENCHMARK.json`` per run.

    python3 benchmarks/chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Builds the cell's stream, weights and traffic from ``--seed``, warms
the kept server, measures for ``--seconds``, then (``--trace 1``) traces a
short slice, and compares what the window served with the plain
reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``check``: each compared number
with its limit, also printed as the last lines of standard error. The run
needs a TPU with as many chips as the cell names: without one it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# the checkout's own compile cache, at a fixed path (the path is part of
# the cache key), whatever the environment says
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# the TPU runtime otherwise writes its logs to a fixed directory shared by
# every process on the machine
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

import jax  # noqa: E402

from chipbench import catalog, driver  # noqa: E402

TRACE_DIR = ROOT / ".chipbench" / "trace"


def finite(x):
    return x if x is None or math.isfinite(x) else None


def result(name: str, rec, check: dict, limits: dict, device: dict,
           trace: bool) -> dict:
    bench = catalog.benchmark()
    metrics = {}
    for spec in catalog.metrics_for(name, trace, bench):
        value = catalog.metric_reader(spec["name"])(rec)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    verdict = {k: {"value": check[k], "limit": limits[k]} for k in limits}
    correct = driver.verdict(rec, check, limits)
    out = {"correct": bool(correct), "attempted": rec.attempted,
           "failed": rec.failed, "metrics": metrics, "device": device}
    if trace and rec.trace:
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    out["check"] = verdict
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = catalog.cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cfg = catalog.config(cell["config"])
    mix = catalog.traffic(cell["traffic"])
    print(f"[{devs[0].platform} {devs[0].device_kind} x{len(devs)}] "
          f"{cell['name']} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace}", file=sys.stderr, flush=True)
    rec, check, device = driver.run_cell(
        cfg, mix, args.seed, args.seconds, bool(args.trace), T0,
        TRACE_DIR)
    out = result(cell["name"], rec, check, cfg["limits"], device,
                 bool(args.trace))
    late = rec.lateness_ms
    passes = sorted(rec.pass_s)
    print(json.dumps({"diagnostics": {
        "setup_s": rec.setup_s, "window_s": rec.window_s, "live": rec.live,
        "launches": rec.launches,
        "pass_s_min_median_max": ([passes[0], passes[len(passes) // 2],
                                   passes[-1]] if passes else None),
        "generator_late_ms_p50_p99_max": (
            [float(x) for x in (sorted(late)[len(late) // 2],
                                sorted(late)[int(0.99 * (len(late) - 1))],
                                max(late))] if late else None),
        "numbers": check,
        "trace_bound": rec.trace_bound,
        "trace_lines": rec.trace.get("lines") if rec.trace else None}}),
        file=sys.stderr)
    # a program lowered inside the window puts compile time into the
    # measured numbers: flagged on every run where it happens
    print(f"lowerings_in_window = {rec.lowerings_in_window}"
          + (f" WARNING: {rec.lowered_in_window[:8]}"
             if rec.lowerings_in_window else ""), file=sys.stderr)
    for k, v in out["check"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(f"correct = {out['correct']} (failed {rec.failed} of "
          f"{rec.attempted})", file=sys.stderr, flush=True)
    out["check"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                    for k, v in out["check"].items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
