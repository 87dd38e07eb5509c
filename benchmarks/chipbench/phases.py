"""Where a cell's serve time goes, read from the program's own spans and
stamps (``repro.serve.spans``): the serve loop's phases per live snapshot
and how much of the run's wall they cover, host prep's thread CPU time,
the open-loop sojourn split into its parts, the device's idle time by the
serve span the loop's thread was in, and the device time of the glue ops
by named scope.

    python3 benchmarks/chipbench/phases.py --workload <cell> --seed <n> \\
        --seconds <s>

One process: the cell's warm-up, a window of ``--seconds`` on the seeds
``run.py`` uses, then one traced slice as ``run.py --trace 1`` takes it.
Prints one JSON object. The readers are functions of ``ServeStats`` and
of the profile's events, so that the harness's ``Record`` can take them
up as they are.
"""
from __future__ import annotations

import argparse
import collections
import json
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    # as a script: the checkout's import paths and compile cache, as
    # run.py sets them
    import run  # noqa: F401

from chipbench import driver, tracing, traffic  # noqa: E402

PREFIX = "serve."
#: the replay loop's phases, per live snapshot, and the spans each sums
PARTS = {
    "producer_wait": ("serve.wait_producers",),
    "stage": ("serve.stage", "serve.stack_batch"),
    "dispatch": ("serve.dispatch",),
    "device_wait": ("serve.device_wait",),
    "unstage": ("serve.unstage", "serve.commit", "serve.checkpoint"),
}
#: spans the serve loop's thread opens outside any other
TOP = ("serve.wait_producers", "serve.admit", "serve.idle", "serve.pool",
       "serve.launch", "serve.express", "serve.spawn", "serve.shutdown")
#: the children of ``serve.launch``
LAUNCH = ("serve.checkpoint", "serve.stage", "serve.stack_batch",
          "serve.dispatch", "serve.device_wait", "serve.unstage",
          "serve.commit")
SCOPES = ("edge_aggregate", "edge_project", "head")
NO_SPAN = "(no span)"


# ------------------------------------------------------------ counters ----

def totals(stats_list: list) -> dict:
    """The runs' span totals, walls, live snapshots and host prep."""
    ms, n = collections.Counter(), collections.Counter()
    out = {"total_ms": 0.0, "live": 0, "prep_ms": 0.0, "prep_cpu_ms": 0.0,
           "prepared": 0}
    for st in stats_list:
        ms.update(st.phase_ms)
        n.update(st.phase_n)
        out["total_ms"] += st.total_ms
        out["live"] += st.live_snapshots
        out["prep_ms"] += float(np.sum(st.preprocess_ms))
        out["prep_cpu_ms"] += float(np.sum(st.preprocess_cpu_ms))
        out["prepared"] += len(st.preprocess_cpu_ms)
    out["phase_ms"], out["phase_n"] = dict(ms), dict(n)
    return out


def per_snapshot(tot: dict) -> dict:
    """The loop's phases in ms per live snapshot (``PARTS``), their share
    of the runs' wall, the share of every span the loop's thread opens
    (``TOP``), and the rest of the wall by where it lies: inside
    ``serve.launch`` but outside its phases, producer start and shutdown,
    and outside every span (grouping, scheduling, the stats)."""
    live = tot["live"]
    if not live:
        return {}
    ms = tot["phase_ms"]
    parts = {k: sum(ms.get(s, 0.0) for s in spans) / live
             for k, spans in PARTS.items()}
    top = sum(ms.get(s, 0.0) for s in TOP)
    in_launch = ms.get("serve.launch", 0.0) - sum(ms.get(s, 0.0)
                                                  for s in LAUNCH)
    return {"ms_per_snap": parts,
            "covered_pct": 100.0 * sum(parts.values()) * live
            / tot["total_ms"],
            "top_covered_pct": 100.0 * top / tot["total_ms"],
            "rest_ms_per_snap": {
                "launch_outside_phases": in_launch / live,
                "spawn": ms.get("serve.spawn", 0.0) / live,
                "shutdown": ms.get("serve.shutdown", 0.0) / live,
                "outside_loop_spans": (tot["total_ms"] - top) / live},
            "prep_cpu_ms_per_snap": (tot["prep_cpu_ms"] / tot["prepared"]
                                     if tot["prepared"] else None),
            "prep_ms_per_snap": (tot["prep_ms"] / tot["prepared"]
                                 if tot["prepared"] else None)}


def online_parts(sched: list, stats) -> dict:
    """Each committed snapshot's sojourn split at its stamps, in ms:
    ``late`` (due -> arrive: the arrival generator), ``arrive_to_ready``
    (host prep), ``ready_to_launch`` (producer queue, backlog, tick) and
    ``launch_to_commit``; with ``sojourn`` as ``traffic.sojourns_ms``
    computes it. The four parts add up to the sojourn."""
    out = {k: [] for k in ("late", "arrive_to_ready", "ready_to_launch",
                           "launch_to_commit", "sojourn")}
    for i, (_, due) in enumerate(sched):
        sid = f"t{i:02d}"
        commit = stats.commit_ms.get(sid, [])
        soj = traffic.sojourns_ms(due, commit)
        k = soj.size
        due_ms = 1e3 * np.asarray(due[:k], np.float64)
        arrive = np.asarray(stats.arrive_ms.get(sid, [])[:k], np.float64)
        ready = np.asarray(stats.ready_ms.get(sid, [])[:k], np.float64)
        launch = np.asarray(stats.launch_start_ms.get(sid, [])[:k],
                            np.float64)
        out["late"] += (arrive - due_ms).tolist()
        out["arrive_to_ready"] += (ready - arrive).tolist()
        out["ready_to_launch"] += (launch - ready).tolist()
        out["launch_to_commit"] += (np.asarray(commit[:k], np.float64)
                                    - launch).tolist()
        out["sojourn"] += soj.tolist()
    return out


# ------------------------------------------------------------- profile ----

def _lines(events: list) -> tuple:
    """``(device ops per device pid, host events per (pid, tid), the
    serve loop's line)``: the loop's line is the one that holds the
    traced slice's span."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[e["pid"], e["tid"]] = e["args"]["name"]
    dev = collections.defaultdict(list)
    host = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        if procs.get(e["pid"], "").startswith("/device:"):
            if threads.get((e["pid"], e["tid"]), "").endswith("XLA Ops"):
                dev[e["pid"]].append(e)
        else:
            host[e["pid"], e["tid"]].append(e)
    loop = [evs for evs in host.values()
            if any(e["name"] == tracing.SLICE_NAME for e in evs)]
    return dev, host, loop[0] if loop else []


def _window(loop: list) -> tuple | None:
    spans = [e for e in loop if e["name"] == tracing.SLICE_NAME]
    if not spans:
        return None
    return (min(e["ts"] for e in spans),
            max(e["ts"] + e["dur"] for e in spans))


def innermost_segments(spans: list) -> list:
    """Disjoint ``(start, end, name)`` pieces of the time that
    ``spans`` (``(start, end, name)`` of one thread, so nested) cover,
    each named by the innermost span over it, in time order."""
    out, stack, t = [], [], 0.0
    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            end, inner = stack.pop()
            if t < end:
                out.append((t, end, inner))
                t = end
        if stack and t < a:
            out.append((t, a, stack[-1][1]))
        stack.append((b, name))
        t = a
    while stack:
        end, inner = stack.pop()
        if t < end:
            out.append((t, end, inner))
            t = end
    return out


def span_gaps(events: list) -> list:
    """The device's idle time in the traced slice by the innermost
    ``serve.*`` span of the serve loop's thread over it (``(no span)``
    where there is none), ``[label, seconds]`` largest first, averaged
    over chips. An idle gap that spans several phases is split among
    them."""
    dev, _, loop = _lines(events)
    win = _window(loop)
    if win is None or not dev:
        return []
    lo, hi = win
    segs = innermost_segments([(e["ts"], e["ts"] + e["dur"], e["name"])
                               for e in loop
                               if e["name"].startswith(PREFIX)])
    by = collections.defaultdict(float)
    for evs in dev.values():
        busy = tracing.union(tracing.clip(
            [(e["ts"], e["ts"] + e["dur"]) for e in evs], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        j = 0
        for a, b in zip(edges[0::2], edges[1::2]):
            covered = 0.0
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < b:
                d = min(b, segs[k][1]) - max(a, segs[k][0])
                if d > 0:
                    by[segs[k][2]] += d
                    covered += d
                k += 1
            if b - a - covered > 0:
                by[NO_SPAN] += b - a - covered
    n = len(dev)
    return [[k, v / n / 1e6] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])]


def _varint(b: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return out, i


def _pb(b: bytes) -> list:
    """``(field number, value)`` pairs of one protobuf message in wire
    format: an int for a varint, bytes for any other field."""
    out, i = [], 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        out.append((key >> 3, v))
    return out


def _first(fields: list, number: int, default=b""):
    return next((v for f, v in fields if f == number), default)


def _hlo_op_names(hlo_proto: bytes) -> tuple:
    """``(module name, {instruction: op_name})`` of one serialized
    ``HloProto``: module 1 > name 1, computations 3 > instructions 2 >
    name 1, metadata 7 > op_name 2."""
    module = _pb(_first(_pb(hlo_proto), 1))
    names = {}
    for f, comp in module:
        if f != 3:
            continue
        for g, inst in _pb(comp):
            if g == 2:
                fields = _pb(inst)
                op_name = _first(_pb(_first(fields, 7)), 2)
                if op_name:
                    names[_first(fields, 1).decode()] = op_name.decode()
    return _first(module, 1).decode(), names


def hlo_op_names(trace_dir) -> dict:
    """``{module: {instruction: op_name}}`` of every program the profile
    under ``trace_dir`` ran: the ``op_name`` of each instruction's
    metadata, named scopes included, read from the HLO protos that the
    profile's ``/host:metadata`` plane keeps (``ProfileData`` does not
    show them). A module is keyed by its name and by the plane's
    ``name(program id)``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    out: dict = {}
    if len(files) != 1:
        return out
    for f, plane in _pb(files[0].read_bytes()):
        fields = _pb(plane) if f == 1 else []
        if _first(fields, 2) != b"/host:metadata":
            continue
        stat_names = {}
        for g, entry in fields:
            if g == 5:  # stat metadata: id 1, name 2
                meta = _pb(_first(_pb(entry), 2))
                stat_names[_first(meta, 1, 0)] = _first(meta, 2).decode()
        for g, entry in fields:
            if g != 4:  # event metadata: name 2, stats 5
                continue
            meta = _pb(_first(_pb(entry), 2))
            for h, stat in meta:
                stat = _pb(stat) if h == 5 else []
                if stat and stat_names.get(_first(stat, 1, 0)) == "Hlo Proto":
                    module, names = _hlo_op_names(_first(stat, 6))
                    out[module] = names
                    out[_first(meta, 2).decode()] = names
    return out


_INSTRUCTION = re.compile(r"^%?(?P<name>[^\s=]+) = ")


def _op_names(e: dict, op_names: dict) -> list:
    """The ``op_name`` metadata of a device op: its module from the
    event's ``hlo_module``, else every module that has its instruction."""
    args = e.get("args", {})
    m = _INSTRUCTION.match(e["name"])
    inst = args.get("hlo_op") or (m.group("name") if m else e["name"])
    module = args.get("hlo_module")
    tables = ([op_names[module]] if module in op_names
              else list(op_names.values()))
    return [t[inst] for t in tables if inst in t]


def scope_ops(events: list, scope: str, op_names: dict | None = None
              ) -> dict:
    """Device time (s, averaged over chips) of each op in the traced
    slice that is not the stream-engine kernel and whose metadata names
    ``scope`` as a path component: a string argument of the event, or
    its instruction's ``op_name`` in ``op_names`` (``hlo_op_names``)."""
    dev, _, loop = _lines(events)
    win = _window(loop)
    if win is None or not dev:
        return {}
    lo, hi = win
    mark = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    ops = collections.defaultdict(float)
    for evs in dev.values():
        for e in evs:
            if (e["ts"] + e["dur"] <= lo or e["ts"] >= hi
                    or tracing.is_kernel(e)):
                continue
            meta = [v for v in e.get("args", {}).values()
                    if isinstance(v, str)]
            meta += _op_names(e, op_names or {})
            if any(mark.search(v) for v in meta):
                ops[e["name"]] += e["dur"]
    return {k: v / len(dev) / 1e6 for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])}


def scope_s(events: list, scope: str, op_names: dict | None = None
            ) -> float:
    """Total device time (s) of the ops ``scope_ops`` finds."""
    return float(sum(scope_ops(events, scope, op_names).values()))


# ------------------------------------------------------------------ run ----

def _serve_window(runner, mix: dict, seed: int, seconds: float) -> tuple:
    """The window's runs, on ``run.py``'s seeds: ``([stats], [sched])``
    (``sched`` for an open-loop window)."""
    if mix["mode"] == "replay":
        rng = traffic.rng_for(seed, 3)
        t0, stats = time.perf_counter(), []
        while True:
            _, streams = runner._streams(runner._offsets(rng))
            stats.append(runner.serve(streams)[2])
            if time.perf_counter() - t0 >= seconds:
                return stats, []
    sched, _, _, st, _ = runner.segment(traffic.rng_for(seed, 3),
                                        mix["rate_per_s"], seconds)
    return [st], [sched]


def _serve_slice(runner, mix: dict, seed: int):
    """The traced slice's run (``run.py``'s): its ``ServeStats``."""
    if mix["mode"] == "replay":
        _, streams = runner._streams(runner._offsets(
            traffic.rng_for(seed, 4)))
        return runner.serve(streams)[2]
    return runner.segment(traffic.rng_for(seed, 4), mix["rate_per_s"],
                          runner.TRACE_SECONDS)[3]


def measure(cfg: dict, mix: dict, seed: int, seconds: float,
            trace_dir) -> dict:
    """Warm the cell, serve the window, trace one slice; the readings."""
    import jax

    cell = driver.Cell(cfg, mix, seed)
    runner = cell.runner
    with cell.precision():
        runner.warm()
        t0 = time.perf_counter()
        stats, scheds = _serve_window(runner, mix, seed, seconds)
        window_s = time.perf_counter() - t0
        shutil.rmtree(trace_dir, ignore_errors=True)
        t0 = time.perf_counter()
        with jax.profiler.trace(str(trace_dir)):
            with jax.profiler.TraceAnnotation(tracing.SLICE_NAME):
                sliced = _serve_slice(runner, mix, seed)
        slice_wall_s = time.perf_counter() - t0
        events = tracing.load(trace_dir)
        op_names = hlo_op_names(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    tot = totals(stats)
    out = {"window_s": window_s, "live": tot["live"],
           "total_ms": tot["total_ms"], **per_snapshot(tot),
           "phase_ms": tot["phase_ms"], "phase_n": tot["phase_n"]}
    if scheds:
        parts = online_parts(scheds[0], stats[0])
        summed = np.sum([parts[k] for k in parts if k != "sojourn"], axis=0)
        out["parts_p50_ms"] = {k: float(np.percentile(v, 50))
                               for k, v in parts.items() if v}
        out["telescope_max_err_ms"] = (
            float(np.abs(summed - np.asarray(parts["sojourn"])).max())
            if parts["sojourn"] else None)
    red = tracing.reduce(events, driver.program_files())
    gaps = span_gaps(events)
    idle = sum(v for _, v in gaps)
    out["slice"] = {
        "wall_s": slice_wall_s, "window_s": red["window_s"],
        "busy_s": red["busy_s"], "kernel_s": red["kernel_s"],
        "glue_s": red["glue_s"], "live": sliced.live_snapshots,
        "idle_s": idle, "idle_by_span": gaps,
        "idle_in_span_pct": (100.0 * (1.0 - dict(gaps).get(NO_SPAN, 0.0)
                                      / idle) if idle else None),
        "glue_by_scope": {s: scope_s(events, s, op_names) for s in SCOPES},
        "scoped_ops": {s: list(scope_ops(events, s, op_names).items())[:6]
                       for s in SCOPES}}
    if sliced.live_snapshots:
        out["slice"]["edge_aggregate_ms_per_snap"] = (
            1e3 * out["slice"]["glue_by_scope"]["edge_aggregate"]
            / sliced.live_snapshots)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from run import TRACE_DIR  # sets the cache and import paths

    import jax

    from chipbench import catalog

    if jax.devices()[0].platform != "tpu":
        print("phases: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cell = catalog.cell(args.workload)
    out = measure(catalog.config(cell["config"]),
                  catalog.traffic(cell["traffic"]), args.seed, args.seconds,
                  TRACE_DIR.parent / "phases")
    print(json.dumps({"workload": cell["name"], "seed": args.seed, **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
