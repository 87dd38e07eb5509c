"""Finds an open-loop cell's knee on the chip: the highest aggregate rate
at which at least 99% of the snapshots due in a window commit within it
and the backlog does not grow. One process and one kept server serve the
cell's traffic at each rate in turn.

    python3 benchmarks/chipbench/sweep.py --workload <open-loop cell> \\
        --seed <n> --seconds <s> --rates 100,200,400

Each rate is served first for a short warm segment, then measured. Prints
one JSON line per rate: snapshots due, committed inside the window,
sojourn p50/p95, the drain past the window's end, the backlog trend
(median sojourn of the last quarter of due times over the first's), how
late the arrival generator ran (it blocks once the server's queues are
full), and what lowered inside the measured segment. A rate is
sustained when, on every seed swept, at least 99% of the snapshots due
commit inside the segment and the p95 sojourn stays within 5x that of
the lowest rate swept; the knee is the highest rate sustained with every
rate below it. The cell's traffic file then takes 0.8x the knee as its
``rate_per_s``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT  # noqa: E402,F401  (sets the cache and import paths)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import catalog, driver, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cell = catalog.cell(args.workload)
    cfg, mix = catalog.config(cell["config"]), catalog.traffic(cell["traffic"])
    c = driver.Cell(cfg, mix, args.seed)
    counter = driver.lowerings()
    with c.precision():
        c.runner.warm()
        print(json.dumps({"setup_s": time.perf_counter() - T0}), flush=True)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            c.runner.segment(traffic.rng_for(args.seed, 50 + k), rate,
                             c.runner.WARM_SECONDS)
            before = counter.count
            late: list = []
            sched, _, _, stats, wall = c.runner.segment(
                traffic.rng_for(args.seed, 10 + k), rate, args.seconds, late)
            due, soj = [], []
            for i, (_, d) in enumerate(sched):
                s = traffic.sojourns_ms(d, stats.commit_ms.get(f"t{i:02d}",
                                                               []))
                due.extend(d[:len(s)].tolist())
                soj.extend(s.tolist())
            due, soj = np.asarray(due), np.asarray(soj)
            n_due = sum(len(d) for _, d in sched)
            inside = int(np.sum(1e-3 * soj + due <= args.seconds))
            q = args.seconds / 4
            first = soj[due < q]
            last = soj[due >= 3 * q]
            print(json.dumps({
                "rate": rate, "due": n_due, "committed": int(soj.size),
                "inside_pct": 100.0 * inside / max(1, n_due),
                "p50_ms": float(np.percentile(soj, 50)) if soj.size else None,
                "p95_ms": float(np.percentile(soj, 95)) if soj.size else None,
                "drain_s": wall - args.seconds,
                "trend": (float(np.median(last) / np.median(first))
                          if first.size and last.size else None),
                "launches": stats.launches, "live": stats.live_snapshots,
                "padded": stats.padded_snapshots,
                "late_ms_p50_p99": ([float(np.percentile(late, q))
                                     for q in (50, 99)] if late else None),
                "lowerings": counter.count - before,
                "lowered": counter.names[before:][:8]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
