"""Reduce a JAX profiler trace to where a serve's time went.

Reads the Chrome-format trace (``*.trace.json.gz``) that
``jax.profiler.trace(log_dir)`` writes under
``log_dir/plugins/profile/<run>/`` and prints, per device line (``XLA
Modules``, ``XLA Ops``), the busy time (union of op intervals) over its
span and the ops with the most total time, each with the source line that
emitted it; and, for the host's Python thread, the frames with the most
inclusive time (tracing, lowering and the serve loop show up here; a
recursive frame counts once per nesting level, so its total can pass the
span).

    python benchmarks/trace_summary.py TRACE.json.gz [--top N]
"""
from __future__ import annotations

import argparse
import collections
import gzip
import json


def busy_ms(intervals: list) -> float:
    """Length of the union of ``(start, end)`` intervals (µs), in ms."""
    busy, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            busy += b - max(a, end)
            end = b
    return busy / 1e3


def summarize(events: list, top: int = 8) -> dict:
    """``{line: {"busy_ms", "span_ms", "n", "top": [(name, ms, count,
    source)]}}`` for every device line and every host Python thread of a
    trace."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e["name"] == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e["name"] == "thread_name":
            threads[e["pid"], e["tid"]] = e["args"]["name"]
    lines = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        thread = threads.get((e["pid"], e["tid"]), "")
        if proc.startswith("/device:") or thread == "python":
            lines[f"{proc} {thread}"].append(e)
    out = {}
    for line, evs in sorted(lines.items()):
        spans = [(e["ts"], e["ts"] + e["dur"]) for e in evs]
        # op names are unique only within one XLA module: key on the
        # emitting source line as well
        totals = collections.defaultdict(lambda: [0.0, 0])
        for e in evs:
            t = totals[e["name"], e.get("args", {}).get("source", "")]
            t[0] += e["dur"] / 1e3
            t[1] += 1
        ranked = sorted(totals.items(), key=lambda kv: -kv[1][0])[:top]
        out[line] = {"busy_ms": busy_ms(spans),
                     "span_ms": (max(b for _, b in spans)
                                 - min(a for a, _ in spans)) / 1e3,
                     "n": len(evs),
                     "top": [(name, ms, n, src)
                             for (name, src), (ms, n) in ranked]}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a *.trace.json.gz written by "
                                  "jax.profiler.trace")
    ap.add_argument("--top", type=int, default=8)
    args = ap.parse_args(argv)
    with gzip.open(args.trace) as f:
        events = json.load(f)["traceEvents"]
    for line, s in summarize(events, args.top).items():
        print(f"{line}: {s['n']} events, busy {s['busy_ms']:.3f} ms over "
              f"span {s['span_ms']:.3f} ms")
        for name, ms, n, src in s["top"]:
            print(f"    {ms:12.3f} ms  x{n:<5d} {name}"
                  + (f"  ({src})" if src else ""))


if __name__ == "__main__":
    main()
