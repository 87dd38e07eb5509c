"""Reduces a JAX profiler trace of the traced slice to device time.

The interval arithmetic (busy time as the union of op intervals on a
device line, op totals by name) is a copy of the repository's
``benchmarks/trace_summary.py``, kept here so that no change to the
program moves the yardstick. Added here: the split of device time into
the stream-engine kernel (ops whose name or arguments carry the kernel's
name, ``stream_engine_<family>``) and everything else (the glue ops),
and the idle gaps between device ops, each labelled by what the host's
main Python thread was doing in it.

Reads the ``*.xplane.pb`` that ``jax.profiler.trace`` writes. Device
lines are the ``XLA Ops`` lines of ``/device:`` planes; host frames are
the events of the main thread (the one that holds the slice's span),
named ``$<file>:<line> <function>``.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

KERNEL_MARK = "stream_engine"
#: the host span that the harness wraps around the traced slice
SLICE_NAME = "chipbench_traced_slice"
_FRAME = re.compile(r"^\$(?P<file>[^ :]+)(?::\d+)? (?P<func>.+)$")


def load(trace_dir: Path) -> list:
    """The events of the one profile under ``trace_dir``, read from its
    ``*.xplane.pb`` (every event, where the Chrome-format export may cap
    them) into Chrome-format dicts: a process per plane, a thread per
    line, times in µs, string-valued stats of device events as ``args``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one *.xplane.pb under "
                                f"{trace_dir}, found {len(files)}")
    events = []
    for pid, plane in enumerate(ProfileData.from_file(str(files[0])).planes):
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "args": {"name": plane.name}})
        device = plane.name.startswith("/device:")
        for tid, line in enumerate(plane.lines):
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": line.name}})
            for e in line.events:
                ev = {"ph": "X", "pid": pid, "tid": tid, "name": e.name,
                      "ts": e.start_ns / 1e3, "dur": e.duration_ns / 1e3}
                if device:
                    ev["args"] = {k: v for k, v in e.stats
                                  if isinstance(v, str)}
                events.append(ev)
    return events


def union(intervals: list) -> list:
    """Merged ``(start, end)`` intervals, sorted."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def is_kernel(e: dict) -> bool:
    if KERNEL_MARK in e.get("name", ""):
        return True
    return any(isinstance(v, str) and KERNEL_MARK in v
               for v in e.get("args", {}).values())


def frame(name: str):
    """``(file, function)`` of a Python-frame event name, else None."""
    m = _FRAME.match(name)
    return (m.group("file"), m.group("func")) if m else None


def reduce(events: list, program_files: frozenset = frozenset(),
           top: int = 10) -> dict:
    """Device and host figures of a traced slice (seconds):

    ``window_s``  the slice's host span (the ``SLICE_NAME`` event);
    ``busy_s``    union of device-op intervals in it, averaged over chips;
    ``kernel_s``  total duration of stream-engine kernel ops;
    ``glue_s``    total duration of every other device op;
    ``device_ops`` the ``top`` op names by total duration;
    ``idle_gaps`` the ``top`` labels by total idle time, a gap labelled by
                  the innermost program frame (a file in
                  ``program_files``) and the innermost frame of the main
                  thread at the gap's midpoint;
    ``chips``     device lines found. Device fields are None without any;
    ``lines``     process/thread names of the profile, for diagnosis.
    """
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[e["pid"], e["tid"]] = e["args"]["name"]
    names = sorted(f"{procs.get(p, '')}/{name}"
                   for (p, _), name in threads.items())[:40]
    dev = collections.defaultdict(list)
    lines = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        thread = threads.get((e["pid"], e["tid"]), "")
        if proc.startswith("/device:"):
            if thread.endswith("XLA Ops"):
                dev[e["pid"]].append(e)
        else:
            lines[e["pid"], e["tid"]].append(e)
    # the main thread: the one that holds the slice's span (else the
    # thread the profiler names "python")
    main = [k for k, evs in lines.items()
            if any(e["name"] == SLICE_NAME for e in evs)]
    main = main or [k for k in lines if threads.get(k) == "python"]
    host = lines[main[0]] if main else []
    slices = [e for e in host if e["name"] == SLICE_NAME]
    if slices:
        lo = min(e["ts"] for e in slices)
        hi = max(e["ts"] + e["dur"] for e in slices)
    elif host:
        lo = min(e["ts"] for e in host)
        hi = max(e["ts"] + e["dur"] for e in host)
    else:
        return {"window_s": None, "busy_s": None, "kernel_s": None,
                "glue_s": None, "device_ops": [], "idle_gaps": [],
                "chips": 0, "lines": names}
    out = {"window_s": (hi - lo) / 1e6, "chips": len(dev), "lines": names}
    if not dev:
        out.update(busy_s=None, kernel_s=None, glue_s=None, device_ops=[],
                   idle_gaps=[])
        return out
    busy, kern, glue = 0.0, 0.0, 0.0
    ops = collections.defaultdict(float)
    gaps = []
    for evs in dev.values():
        evs = [e for e in evs if e["ts"] + e["dur"] > lo and e["ts"] < hi]
        merged = union(clip([(e["ts"], e["ts"] + e["dur"]) for e in evs],
                            lo, hi))
        busy += sum(b - a for a, b in merged)
        for e in evs:
            if is_kernel(e):
                kern += e["dur"]
            else:
                glue += e["dur"]
            ops[e["name"]] += e["dur"]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [((a + b) / 2, b - a)
                 for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    by_label = collections.defaultdict(float)
    for label, (_, d) in zip(label_points([t for t, _ in gaps], host,
                                          program_files), gaps):
        by_label[label] += d
    n = len(dev)
    out.update(
        busy_s=busy / n / 1e6, kernel_s=kern / n / 1e6, glue_s=glue / n / 1e6,
        device_ops=[[k, v / n / 1e6] for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v / n / 1e6] for k, v in
                   sorted(by_label.items(), key=lambda kv: -kv[1])[:top]])
    return out


def _innermost(points: list, frames: list) -> list:
    """For each sorted time in ``points``, the shortest of ``frames``
    (``(start, end, label)``) that contains it, or None."""
    out = [None] * len(points)
    nxt = list(range(len(points) + 1))  # next point not yet labelled

    def find(i):
        while nxt[i] != i:
            nxt[i] = nxt[nxt[i]]
            i = nxt[i]
        return i

    for a, b, label in sorted(frames, key=lambda f: f[1] - f[0]):
        i = find(bisect.bisect_left(points, a))
        while i < len(points) and points[i] <= b:
            out[i] = label
            nxt[i] = i + 1
            i = find(i + 1)
    return out


def label_points(times: list, host: list, program_files: frozenset) -> list:
    """What the main thread was doing at each of ``times``: its innermost
    program frame, then its innermost frame when that differs."""
    order = sorted(range(len(times)), key=times.__getitem__)
    points = [times[i] for i in order]
    frames = []
    for e in host:
        f = frame(e["name"])
        if f:
            frames.append((e["ts"], e["ts"] + e["dur"], f"{f[0]}:{f[1]}",
                           f[0] in program_files))
    inner = _innermost(points, [f[:3] for f in frames])
    prog = _innermost(points, [f[:3] for f in frames if f[3]])
    labels = [None] * len(times)
    for k, i in enumerate(order):
        a, b = prog[k], inner[k]
        labels[i] = ("(no host frame)" if b is None
                     else b if a is None or a == b else f"{a} > {b}")
    return labels


def idle_pct(trace) -> float | None:
    """Device idle share of the traced slice, in %."""
    if not trace or trace.get("busy_s") is None or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
