"""One cell, end to end: set-up, warm-up, the measured window, the traced
slice, and the comparison with the plain reference that decides
``correct``.

The window drives ``SnapshotServer.run_multi`` on one server, built once
as ``SnapshotServer(session=BoosterSession(cfg, plan(cfg, ...)))`` and
kept for the whole run, under the matrix-multiply precision that the
configuration states. Two traffic modes, named by the mix's ``mode``:

``replay``     closed loop: passes of ``tenants`` streams, each a
               ``history``-long run of the graph stream from a seeded
               offset with a fresh state, back to back until ``--seconds``
               have passed.
``open_loop``  one ``run_multi`` over the window: every tenant's snapshots
               are yielded at their due times (``traffic.open_loop``);
               arrivals stop at the window's end and the server drains what
               is queued. Sojourn runs from a snapshot's due time to its
               commit (``ServeStats.commit_ms``).

Warm-up goes through the same server and the same ``run_multi`` entry, on
traffic of the cell's own form, so it reaches the launch shapes that the
server itself composes and no others. The program is compiled into the
persistent cache inside the checkout, so only a checkout's first run
compiles.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import shutil
import time
from pathlib import Path

import jax
import numpy as np

from chipbench import catalog, refcore, tracing, traffic

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Lowerings:
    """Counts the programs JAX lowers (each new jitted function or shape)
    and keeps their names. JAX's listeners are process-wide, so one
    counter serves the process."""

    def __init__(self):
        self.count = 0
        self.names: list = []

    def __call__(self, event: str, duration_secs: float, **kwargs) -> None:
        del duration_secs
        if event == LOWER_EVENT:
            self.count += 1
            self.names.append(str(kwargs.get("fun_name", "?")))


@functools.lru_cache(maxsize=1)
def lowerings() -> Lowerings:
    counter = Lowerings()
    jax.monitoring.register_event_duration_secs_listener(counter)
    return counter


@dataclasses.dataclass
class Record:
    """What a run measured; the metric readers (``metrics/*.py``) take
    their numbers from it."""

    mode: str
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    live: int = 0
    padded: int = 0
    launches: int = 0
    prep_ms: float = 0.0
    prep_count: int = 0
    serve_ms: float = 0.0
    launch_ms: float = 0.0
    flops: float = 0.0
    sojourn_ms: list = dataclasses.field(default_factory=list)
    lateness_ms: list = dataclasses.field(default_factory=list)
    lowerings_in_window: int = 0
    lowered_in_window: list = dataclasses.field(default_factory=list)
    pass_s: list = dataclasses.field(default_factory=list)
    trace: dict | None = None
    trace_live: int = 0
    trace_flops: float = 0.0
    trace_bytes: float = 0.0
    trace_bound: str | None = None

    def add(self, stats) -> None:
        self.live += stats.live_snapshots
        self.padded += stats.padded_snapshots
        self.launches += stats.launches
        self.prep_ms += float(np.sum(stats.preprocess_ms))
        self.prep_count += len(stats.preprocess_ms)
        self.serve_ms += stats.total_ms
        # each launch appends its wall / live once per live snapshot
        self.launch_ms += float(np.sum(stats.per_snapshot_ms))


def failures(stats, outs: dict, expected: dict, chunk: int) -> int:
    """Snapshots that were due and not served by a healthy launch: those a
    quarantined tenant never served, and those a degraded launch (solo or
    oracle rung) served, bounded by ``chunk`` per degraded launch."""
    failed = 0
    for sid, n in expected.items():
        served = len(outs.get(sid, ()))
        res = stats.tenants.get(sid)
        degraded = res.degraded_launches if res is not None else 0
        failed += (n - served) + min(served, degraded * chunk)
    return failed


class Stream:
    """The seeded graph stream and what the harness knows of each
    snapshot (active nodes, edges, work)."""

    def __init__(self, snaps: list, work, m: dict):
        self.snaps = snaps
        self.active = [np.unique(np.concatenate([s, d])) for s, d, _ in snaps]
        self.n = [a.size for a in self.active]
        self.e = [s.size for s, _, _ in snaps]
        self.flops = [work.snapshot_flops(m, n, e)
                      for n, e in zip(self.n, self.e)]
        self.bytes = [work.snapshot_bytes(m, n, e)
                      for n, e in zip(self.n, self.e)]

    def keep(self, idx: list, outs: list) -> list:
        """Copies of a stream's served outputs: live rows, and the largest
        magnitude in the padding rows (which must be zero)."""
        kept = []
        for i, o in zip(idx, outs):
            o = np.asarray(o)
            pad = o[self.n[i]:]
            kept.append((np.array(o[:self.n[i]]),
                         float(np.abs(pad).max()) if pad.size else 0.0))
        return kept


class Runner:
    """Traffic of one mix through one kept server."""

    def __init__(self, server, params, fresh, coo: list, stream: Stream,
                 mix: dict, seed: int, chunk: int):
        self.server, self.params, self.fresh = server, params, fresh
        self.coo, self.stream, self.mix = coo, stream, mix
        self.seed, self.chunk = seed, chunk
        self.retained: list = []  # ([sid,] stream indices, outputs, state)

    def serve(self, streams: dict) -> tuple:
        states = {sid: self.fresh for sid in streams}
        return self.server.run_multi(self.params, states, streams)


class Replay(Runner):
    RETAIN_PER_PASS = 2  # streams of each pass kept for the check

    def _streams(self, offsets) -> tuple:
        h = self.mix["history"]
        idx = {f"t{i:02d}": list(range(o, o + h))
               for i, o in enumerate(offsets)}
        return idx, {sid: [self.coo[k] for k in ks] for sid, ks in idx.items()}

    def _offsets(self, rng):
        return traffic.replay_offsets(rng, len(self.coo), self.mix["tenants"],
                                      self.mix["history"])

    def warm(self) -> None:
        _, streams = self._streams(self._offsets(traffic.rng_for(self.seed,
                                                                 2)))
        self.serve(streams)

    def window(self, rec: Record, seconds: float) -> None:
        rng = traffic.rng_for(self.seed, 3)
        t0 = time.perf_counter()
        while True:
            idx, streams = self._streams(self._offsets(rng))
            t_pass = time.perf_counter()
            states, outs, stats = self.serve(streams)
            rec.pass_s.append(time.perf_counter() - t_pass)
            rec.add(stats)
            rec.attempted += sum(len(v) for v in idx.values())
            rec.failed += failures(stats, outs,
                                   {s: len(v) for s, v in idx.items()},
                                   self.chunk)
            rec.flops += sum(self.stream.flops[k] for sid, ks in idx.items()
                             for k in ks[:len(outs[sid])])
            for j in rng.choice(len(idx), self.RETAIN_PER_PASS,
                                replace=False):
                sid = sorted(idx)[j]
                self.retained.append((sid, idx[sid],
                                      self.stream.keep(idx[sid], outs[sid]),
                                      states.get(sid)))
            del states, outs
            if time.perf_counter() - t0 >= seconds:
                break
        rec.window_s = time.perf_counter() - t0

    def trace_slice(self, rec: Record, work, m: dict) -> None:
        """One more pass; its least work from the live graph."""
        idx, streams = self._streams(self._offsets(traffic.rng_for(self.seed,
                                                                   4)))
        _, outs, stats = self.serve(streams)
        rec.trace_live = stats.live_snapshots
        flops = nbytes = 0
        rounds = -(-self.mix["history"] // self.chunk)
        for r in range(rounds):
            nbytes += work.weight_bytes(m)
            for ks in idx.values():
                part = ks[r * self.chunk:(r + 1) * self.chunk]
                rows = np.unique(np.concatenate(
                    [self.stream.active[k] for k in part])).size
                nbytes += work.state_bytes(m, rows)
                nbytes += sum(self.stream.bytes[k] for k in part)
                flops += sum(self.stream.flops[k] for k in part)
        rec.trace_flops, rec.trace_bytes = float(flops), float(nbytes)
        t_f = flops / rec.peaks["flops_per_s"]
        t_b = nbytes / rec.peaks["bytes_per_s"]
        rec.trace_bound = "memory" if t_b >= t_f else "compute"

    def sample(self) -> list:
        """``check_streams`` retained streams, each from another tenant
        slot (a launch row), so that a fault confined to some rows of every
        launch shows."""
        rng = traffic.rng_for(self.seed, 5)
        by_slot: dict = {}
        for r in self.retained:
            by_slot.setdefault(r[0], []).append(r)
        slots = sorted(by_slot)
        pick = [slots[i] for i in rng.permutation(len(slots))]
        return [by_slot[s][rng.integers(len(by_slot[s]))][1:]
                for s in pick[:self.mix["check_streams"]]]


class OpenLoop(Runner):
    WARM_ROUNDS_MAX = 3  # rounds of waves over every launch shape
    WARM_SECONDS = 2.0  # one segment of the cell's own traffic
    WARM_SEGMENTS_MAX = 4
    TRACE_SECONDS = 3.0  # the traced slice

    def segment(self, rng, rate: float, seconds: float,
                lateness: list | None = None) -> tuple:
        """Serve one open-loop segment; returns ``(schedule, states,
        outs, stats, wall_s)``."""
        n = len(self.coo)
        sched = traffic.open_loop(rng, n, self.mix["tenants"], rate, seconds,
                                  self.mix["zipf_s"])
        origin = [0.0]

        def arrivals(off, due):
            for k, d in enumerate(due):
                target = origin[0] + d
                wait = target - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if lateness is not None:
                    lateness.append(1e3 * (time.perf_counter() - target))
                yield self.coo[(off + k) % n]

        streams = {f"t{i:02d}": arrivals(off, due)
                   for i, (off, due) in enumerate(sched)}
        states = {sid: self.fresh for sid in streams}
        origin[0] = time.perf_counter()
        states, outs, stats = self.server.run_multi(self.params, states,
                                                    streams)
        return sched, states, outs, stats, time.perf_counter() - origin[0]

    def warm(self, rate: float | None = None) -> None:
        """The launch shapes the continuous scheduler composes: a tick
        launches the ready tenants (B, padded to a power of two) with up
        to ``stream_chunk`` snapshots each (T, a power of two). Waves of
        B tenants with T snapshots each, for every such B and T, repeat
        while they lower anything new (a tick takes what is ready, so a
        wave also composes smaller launches); then segments of the
        cell's own traffic (at ``rate``, by default the mix's) until one
        lowers nothing new."""
        tenants, n = self.mix["tenants"], len(self.coo)
        counter = lowerings()
        for _ in range(self.WARM_ROUNDS_MAX):
            before = counter.count
            t = self.chunk
            while t >= 1:
                b = 1
                while b < 2 * tenants:
                    b = min(b, tenants)
                    self.serve({f"t{i:02d}": [self.coo[(i + k) % n]
                                              for k in range(t)]
                                for i in range(b)})
                    b *= 2
                t //= 2
            if counter.count == before:
                break
        rng = traffic.rng_for(self.seed, 2)
        for _ in range(self.WARM_SEGMENTS_MAX):
            before = counter.count
            self.segment(rng, rate or self.mix["rate_per_s"],
                         self.WARM_SECONDS)
            if counter.count == before:
                break

    def window(self, rec: Record, seconds: float) -> None:
        rng = traffic.rng_for(self.seed, 3)
        sched, states, outs, stats, wall = self.segment(
            rng, self.mix["rate_per_s"], seconds, rec.lateness_ms)
        rec.window_s = wall
        rec.add(stats)
        expected = {f"t{i:02d}": len(due) for i, (_, due) in enumerate(sched)}
        rec.attempted = sum(expected.values())
        rec.failed = failures(stats, outs, expected, self.chunk)
        for i, (_, due) in enumerate(sched):
            rec.sojourn_ms.extend(traffic.sojourns_ms(
                due, stats.commit_ms.get(f"t{i:02d}", [])).tolist())
        # the check covers the longest stream and a seeded draw of others
        counts = [len(due) for _, due in sched]
        longest = int(np.argmax(counts))
        others = [i for i in traffic.rng_for(self.seed, 5).permutation(
            len(sched)) if i != longest and counts[i] > 0]
        n = len(self.coo)
        for i in [longest] + others[:self.mix["check_streams"] - 1]:
            sid = f"t{i:02d}"
            idx = traffic.walk(n, sched[i][0], len(outs[sid]))
            self.retained.append((idx, self.stream.keep(idx, outs[sid]),
                                  states.get(sid)))

    def trace_slice(self, rec: Record, work, m: dict) -> None:
        del work, m
        _, _, _, stats, _ = self.segment(traffic.rng_for(self.seed, 4),
                                         self.mix["rate_per_s"],
                                         self.TRACE_SECONDS)
        rec.trace_live = stats.live_snapshots

    def sample(self) -> list:
        return self.retained


RUNNERS = {"replay": Replay, "open_loop": OpenLoop}


def gap(got, want) -> float:
    """``max |got - want|`` over ``max(1, max |want|)``; infinite where the
    shapes differ or ``got`` is not finite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    if not got.size:
        return 0.0
    return float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))


def rms_gap(got: list, want: list) -> float:
    """``|got - want| / |want|`` in the 2-norm over all the arrays of
    ``got`` and ``want`` together; infinite where their number or shapes
    differ or ``got`` is not finite."""
    if len(got) != len(want):
        return float("inf")
    num = den = 0.0
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if g.shape != w.shape or not np.isfinite(g).all():
            return float("inf")
        num += float(np.sum((g - w) ** 2))
        den += float(np.sum(w ** 2))
    if den == 0.0:
        return 0.0 if num == 0.0 else float("inf")
    return float(np.sqrt(num / den))


def gaps(outs: list, state, r_outs: list, r_state) -> dict:
    """One stream's numbers against the reference's: the widest output
    and state gaps, and the relative 2-norm gaps of all its outputs and
    of each state leaf (the widest leaf)."""
    g = jax.tree.leaves(jax.tree.map(np.asarray, state))
    w = jax.tree.leaves(r_state)
    if len(g) != len(w):
        return dict.fromkeys(NUMBERS, float("inf"))
    widest = max([gap(a, b) for a, b in zip(outs, r_outs)], default=0.0)
    return {"out_gap": (widest if len(outs) == len(r_outs)
                        else float("inf")),
            "state_gap": max(gap(a, b) for a, b in zip(g, w)),
            "out_rms_gap": rms_gap(outs, r_outs),
            "state_rms_gap": max(rms_gap([a], [b]) for a, b in zip(g, w))}


NUMBERS = ("out_gap", "state_gap", "out_rms_gap", "state_rms_gap")


def compare(ref, params, m: dict, n_global: int, stream: Stream,
            feat: np.ndarray, bucket: tuple, sample: list,
            control: bool = False) -> dict:
    """The numbers that decide ``correct``, over the sampled streams,
    program against the reference at full float32 precision: the widest
    output gap (padding rows held to zero) and final-state gap, and the
    relative 2-norm gaps of a stream's outputs and of its final state
    leaves, each the largest over the streams. With ``control``, the
    same numbers of the reference computed at ``high`` precision in the
    program's place, prefixed ``control_``."""
    out = dict.fromkeys(NUMBERS, 0.0)
    if control:
        out.update({f"control_{k}": 0.0 for k in NUMBERS})
    cache: dict = {}

    def prepared(k):
        if k not in cache:
            cache[k] = refcore.prepare(*stream.snaps[k], *bucket[:2],
                                       n_global)
        return cache[k]

    def fold(prefix, numbers):
        for k, v in numbers.items():
            out[prefix + k] = max(out[prefix + k], v)

    for idx, kept, state in sample:
        graphs = [prepared(k) for k in idx]
        r_outs, r_state = refcore.run_stream(ref, params, m, n_global,
                                             graphs, feat, "highest")
        got = gaps([live for live, _ in kept], state, r_outs, r_state)
        for (_, pad), want in zip(kept, r_outs):
            scale = max(1.0, float(np.abs(want).max())) if want.size else 1.0
            got["out_gap"] = max(got["out_gap"], pad / scale)
        fold("", got)
        if control:
            c_outs, c_state = refcore.run_stream(ref, params, m, n_global,
                                                 graphs, feat, "high")
            fold("control_", gaps(c_outs, c_state, r_outs, r_state))
    return out


def verdict(rec: Record, check: dict, limits: dict) -> bool:
    """A run is correct when it attempted work, every due snapshot was
    served by a healthy launch, and every compared number is within its
    limit."""
    return (rec.attempted > 0 and rec.failed == 0
            and all(check[k] <= limits[k] for k in limits))


def control_numbers(check: dict) -> dict:
    """The control's gaps in the program's place, for ``verdict``: a run
    whose timed path produced what the control does must not be
    correct."""
    return {k: check[f"control_{k}"] for k in NUMBERS}


def program_files() -> frozenset:
    import repro

    return frozenset(p.name for d in repro.__path__
                     for p in Path(d).rglob("*.py"))


class Cell:
    """A cell built for one seed: its graph stream, weights, and one
    kept server behind a runner of the cell's traffic mode. Everything
    that touches the server runs under ``with cell.precision():``."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.api import BoosterSession, plan
        from repro.configs.dgnn import DGNNConfig
        from repro.graph.coo import COOSnapshot
        from repro.serve import SnapshotServer

        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.ref, self.work = catalog.family(cfg["family"])
        self.m = m = cfg["model"]
        fields = {**cfg["plan"], **mix.get("plan", {})}
        self.bucket = (fields["n_pad"], fields["e_pad"], fields["k_max"])
        snaps, self.feat, self.n_global = traffic.graph_stream(
            cfg["dataset"], seed, m["in_dim"], m["edge_dim"], self.bucket)
        self.stream = Stream(snaps, self.work, m)
        coo = [COOSnapshot(src=s, dst=d, edge_feat=e, t_index=i)
               for i, (s, d, e) in enumerate(snaps)]
        key = jax.random.key(int(np.random.SeedSequence([seed, 1])
                                 .generate_state(1)[0]))
        with self.precision():
            self.params = jax.jit(
                lambda k: self.ref.make_params(k, m))(key)
            dcfg = DGNNConfig(**m)
            session = BoosterSession(dcfg, plan(dcfg, **fields),
                                     n_global=self.n_global,
                                     feat_table=self.feat,
                                     params=self.params)
            self.runner = RUNNERS[mix["mode"]](
                SnapshotServer(session=session), self.params,
                session.reset_state(), coo, self.stream, mix, seed,
                fields["stream_chunk"])

    def precision(self):
        return jax.default_matmul_precision(self.cfg["matmul_precision"])

    def check(self, sample: list, control: bool = False) -> dict:
        return compare(self.ref, self.params, self.m, self.n_global,
                       self.stream, self.feat, self.bucket, sample,
                       control=control)


def run_cell(cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
             t0: float, trace_dir: Path, peaks: dict | None = None,
             control: bool = False) -> tuple:
    """Run one cell; returns ``(record, check, device)``: the measured
    record, the compared numbers, and the device description of the
    result line."""
    dev = jax.devices()[0]
    rec = Record(mode=mix["mode"],
                 peaks=peaks if peaks is not None else catalog.peaks(
                     dev.device_kind))
    cell = Cell(cfg, mix, seed)
    runner = cell.runner
    counter = lowerings()
    with cell.precision():
        runner.warm()
        before = counter.count
        t_window = time.perf_counter()
        rec.setup_s = t_window - t0
        runner.window(rec, seconds)
        rec.lowerings_in_window = counter.count - before
        rec.lowered_in_window = counter.names[before:]
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            t_slice = time.perf_counter()
            with jax.profiler.trace(str(trace_dir)):
                with jax.profiler.TraceAnnotation(tracing.SLICE_NAME):
                    runner.trace_slice(rec, cell.work, cell.m)
            t_slice = time.perf_counter() - t_slice
            rec.trace = tracing.reduce(tracing.load(trace_dir),
                                       program_files())
            if rec.trace["window_s"] is None:
                rec.trace["window_s"] = t_slice
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
    sample = runner.sample()
    # the program's state is freed before the reference runs
    del runner
    cell.runner = None
    gc.collect()
    return rec, cell.check(sample, control=control), device
