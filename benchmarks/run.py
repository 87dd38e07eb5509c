"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.
  table4_latency     Table IV  (per-snapshot latency, dataflow vs baseline)
  fig6_ablation      Fig. 6    (baseline / O1 / O2 incremental speedup)
  table7_dse         Table VII (GNN vs RNN module breakdown)
  roofline_table     (ours)    roofline terms per dry-run cell
  compression_bench  (ours)    gradient-compression wire bytes/fidelity
  kernel_bench       (ours)    kernel reference timings + VMEM accounting
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        compression_bench,
        fig6_ablation,
        kernel_bench,
        roofline_table,
        table4_latency,
        table7_dse,
    )

    print("name,us_per_call,derived")
    suites = [
        ("table4", table4_latency.run),
        ("fig6", fig6_ablation.run),
        ("table7", table7_dse.run),
        ("roofline", roofline_table.run),
        ("compression", compression_bench.run),
        ("kernel", kernel_bench.run),
    ]
    failures = []
    for name, fn in suites:
        try:
            for row in fn():
                print(",".join(str(x) for x in row), flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
