"""DGNN snapshot-stream serving engine — the paper's deployment mode.

Implements the §IV-D task-scheduling scheme:
  host thread ("CPU tasks"): slice the temporal COO stream into snapshots,
    renumber + normalize, build ELL, pad into the bucket — irregular,
    control-heavy work;
  device loop ("FPGA tasks"): the jitted DGNN step (format-consuming dense
    compute) pulls prepared snapshots from a DOUBLE-BUFFERED queue, so
    graph loading overlaps inference (the paper's GL/GNN overlap, host
    edition — the in-graph edition is the V1 ping-pong carry).

Bucketed padding: with ``buckets`` set, each snapshot is padded into the
smallest bucket that fits (graph/padding.choose_bucket) instead of the
worst-case shape — small snapshots stop paying big-snapshot compute. The
jit cache holds one compiled step per bucket.

V3 fast path: when the engine runs the time-fused stream dataflow
(plan level "v3" — the stream-engine families), consecutive same-bucket
snapshots are batched into fixed-T chunks (tail padded with no-op empty
snapshots) and the WHOLE chunk is handed to the stream kernel in one
launch, so the recurrent state crosses HBM once per chunk, not per
snapshot.

Multi-tenant batched serving (``run_multi``): many independent clients'
snapshot streams served concurrently. Each client stream gets its own
host preprocessing thread and its own recurrent state store; the device
loop proceeds in rounds, co-buckets each stream's next chunk
(choose_bucket_batch), groups same-bucket chunks across clients, and
hands each group to ONE batched V3 launch — the batch axis is a leading
grid dimension of the stream kernel, so B streams cost one kernel launch
and one weight load while every stream's state store still crosses HBM
exactly twice per chunk. Per-stream outputs are returned in per-stream
order (rounds are sequential and each stream's snapshots are consumed in
order). All three DGNN families take this batched launch through the SAME
stream-engine kernel (kernels/stream_fused.REGISTRY — the model's
``stream_family`` selects its cell spec): GCRN and stacked models keep
their node-state store resident, EvolveGCN its evolving weight matrices
(the in-kernel evolution is live-gated, so the no-op tail snapshots
padding a chunk never advance the weights).

Cross-bucket batching (``promote_buckets``): with bucketed padding, a
round's smaller-bucket chunks may be PROMOTED into the next-larger
occupied bucket — re-padded to the bigger shape so they join that
bucket's in-flight batched launch — trading padding overhead (guarded by
a max padded-compute ratio, graph/padding.promote_bucket_groups) for one
fewer device dispatch per round. The guard compares per-bucket costs:
the static ``bucket_cost`` padded-compute proxy by default, or — with
``promotion_guard="measured"`` in the plan — per-bucket step times from a
tiny warmup calibration (one timed launch per bucket, static proxy kept
as the fallback). ServeStats reports live vs padded snapshot slots and
launch counts per run so the overhead stays visible instead of hiding in
throughput.

Fault isolation and recovery (docs/serve_robustness.md): every chunk
launch goes through a SUPERVISED runner. Snapshots are validated at the
serve boundary (serve/faults.validate_snapshot — malformed input raises a
typed ``SnapshotValidationError`` carrying the tenant id); per-tenant
recurrent state is CHECKPOINTED before each chunk commit and ROLLED BACK
on any failure, so a replayed chunk can never double-evolve state; failed
launches are retried with exponential backoff (plan ``max_retries`` /
``retry_backoff_ms``), bounded by a per-launch deadline (plan
``launch_timeout_ms`` — enforced on completion, overdue results are
discarded, never committed); a persistent fault attributable to one
tenant QUARANTINES that tenant (plan ``supervision="isolate"``) while the
co-batched healthy tenants are transparently retried without the failed
member; an unattributable kernel-path failure walks the graceful
DEGRADATION LADDER (plan ``degrade=True``): batched v3 -> solo v3 -> the
pure-XLA oracle via the kernels/ops force-ref gate, serving
correct-but-slower results instead of erroring. Every recovery action is
visible in ``ServeStats`` (per-tenant errors, retries, rollbacks,
degraded launches, timeouts); the deterministic fault-injection harness
(plan ``fault_plan`` -> serve/faults.FaultInjector) drives each site on
demand so chaos tests pin all of the above.

Configuration is a typed ``repro.api.StreamPlan`` — the server is a
consumer of a ``BoosterSession`` (``SnapshotServer(session=...)``, or the
historical keyword surface, which builds the equivalent plan/session).
Chunk tails and batch-padding rows are expressed through the plan's
ragged-``lengths`` capability: every batched launch carries the true
per-stream lengths and the engine masks the dead slots in-launch, so the
host never manufactures empty tail snapshots.
"""
from __future__ import annotations

import queue
import threading
import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dgnn import DGNNConfig
from repro.core.dataflow import stack_time
from repro.graph.coo import COOSnapshot
from repro.graph.csr import max_in_degree, renumber_and_normalize
from repro.graph.padding import (
    PaddedSnapshot,
    bucket_cost,
    choose_bucket,
    choose_bucket_batch,
    chunk_slab,
    empty_padded,
    pad_snapshot,
    pow2_target,
    promote_bucket_groups,
    slab_row,
    stack_streams,
)
from repro.kernels import ops as kops
from repro.serve.faults import LaunchTimeout, validate_snapshot
from repro.serve.spans import RunTrace
from repro.serve.supervision import SupervisionPolicy, TenantSupervisor

# sid the single-tenant ``run`` path supervises its stream under (one
# namespace for probes/results across both entry points)
SOLO_SID = "stream"

# how long shutdown keeps drain-joining producer threads before giving up
# with a warning (threads cannot be killed in Python; a producer stuck in
# USER iterator code past this is reported, not silently leaked)
_SHUTDOWN_DEADLINE_S = 5.0


@dataclass
class ServeStats:
    per_snapshot_ms: list
    preprocess_ms: list
    total_ms: float
    # no-op-tail waste signal: how many snapshot slots of the batched V3
    # launches were real vs padding (T tails + no-op batch rows), so
    # promoted-bucket and D-blocked rows expose their padding overhead
    # instead of hiding it in throughput.
    live_snapshots: int = 0
    padded_snapshots: int = 0
    promoted_chunks: int = 0  # chunks promoted to a larger bucket
    launches: int = 0         # stream-kernel launches (v3 paths)
    # express-lane signals: static-family chunks are stateless, so they
    # co-batch into dedicated launches with no checkpoint/rollback around
    # them; ``launches_by_family`` splits ALL launches by stream family
    # (express launches count under the express session's family too).
    express_launches: int = 0
    launches_by_family: dict = field(default_factory=dict)
    # fault-isolation / recovery signals (docs/serve_robustness.md)
    retries: int = 0            # failed chunk attempts that were replayed
    rollbacks: int = 0          # per-tenant state rollbacks
    degraded_launches: int = 0  # solo/oracle ladder launches that served
    timeouts: int = 0           # launches past the plan deadline
    # per-tenant outcomes: {sid: supervision.TenantResult} — errors of
    # quarantined tenants, per-tenant recovery counters, output lists
    tenants: dict = field(default_factory=dict)
    # measured-guard calibration fell back to the static proxy (repr of
    # the LAST error; None = calibration ok or never requested)
    calibration_fallback: Optional[str] = None
    # continuous-scheduler signals (docs/serve_scheduler.md)
    ticks: int = 0            # engine ticks (0 under the round scheduler)
    prefill_chunks: int = 0   # backlog chunks served under the prefill quota
    evictions: int = 0        # tenant-state pages spilled to host
    recoveries: int = 0       # tenant-state pages restored from host
    # per-tenant commit timestamps: {sid: [ms since run start, one per
    # committed snapshot, in stream order]} — sojourn latency is this minus
    # the caller's arrival clock (benchmarks/kernel_bench does exactly that)
    commit_ms: dict = field(default_factory=dict)
    # earlier stamps of the same snapshots, same clock and shape: when the
    # producer took it from the tenant's iterator, when it was prepared and
    # queued, and when the launch attempt that served it began
    arrive_ms: dict = field(default_factory=dict)
    ready_ms: dict = field(default_factory=dict)
    launch_start_ms: dict = field(default_factory=dict)
    # named serve-path spans (serve/spans.py): {name: summed wall ms} and
    # {name: count}; per_snapshot_ms is serve.stack_batch + serve.dispatch
    # + serve.device_wait of each launch, spread over its live snapshots
    phase_ms: dict = field(default_factory=dict)
    phase_n: dict = field(default_factory=dict)
    # producer-thread CPU time of each preprocess_ms entry (the wall time
    # less the waits for the GIL)
    preprocess_cpu_ms: list = field(default_factory=list)
    # stream chunks staged for a launch (per attempt), and those whose
    # (T, ...) time stack was a view of a producer's chunk slab, not a copy
    staged_chunks: int = 0
    staged_in_place: int = 0

    def _per_snapshot(self, *names: str) -> float:
        n = len(self.per_snapshot_ms)  # one entry per served snapshot
        return sum(self.phase_ms.get(k, 0.0) for k in names) / n if n else 0.0

    @property
    def stage_ms_per_snapshot(self) -> float:
        """Host staging of the launches (``serve.stage`` and
        ``serve.stack_batch``) per served snapshot."""
        return self._per_snapshot("serve.stage", "serve.stack_batch")

    @property
    def stage_in_place_pct(self) -> float:
        """Share of the staged stream chunks stacked in place, in %."""
        return (100.0 * self.staged_in_place / self.staged_chunks
                if self.staged_chunks else 0.0)

    @property
    def device_wait_ms_per_snapshot(self) -> float:
        """Wait on the device's result (``serve.device_wait``) per served
        snapshot."""
        return self._per_snapshot("serve.device_wait")

    @property
    def tenant_errors(self) -> dict:
        """{sid: error} for every quarantined tenant."""
        return {sid: r.error for sid, r in self.tenants.items() if not r.ok}


@jax.jit
def _stack_states(states: tuple):
    """The launch's tenant states stacked on a leading B axis in one
    dispatch; op by op it took one for each row and leaf, and each gives
    up the GIL to the producer threads."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


class _ChunkSlabs:
    """One producer's chunk slabs: snapshot j of its stream is padded into
    row ``j % depth`` of a slab (``graph.padding.chunk_slab``) made fresh
    every ``depth`` snapshots. A chunk the loop pulls in steps of ``depth``
    from the stream's start is then consecutive rows of one slab, which
    ``stack_time`` takes as a view: the loop thread copies nothing on the
    T axis. Slabs are never reused, so a chunk's rows keep their slab alive
    and unchanged for as long as a launch or a retry reads them."""

    def __init__(self, depth: int):
        self.depth = depth
        self.slab, self.shape, self.j = None, None, 0

    def row(self, *shape) -> PaddedSnapshot:
        """The next row, for a snapshot of ``shape`` = (n_pad, e_pad,
        k_max, din, de)."""
        if self.slab is None or self.j == self.depth or shape != self.shape:
            self.slab = chunk_slab(self.depth, *shape)
            self.shape, self.j = shape, 0
        self.j += 1
        return slab_row(self.slab, self.j - 1)


class SnapshotServer:
    """Streaming DGNN inference over a snapshot iterator.

    A consumer of ``repro.api.BoosterSession``: all policy — dataflow
    level, tiling, buckets, chunking, promotion, fault
    isolation/recovery — comes from the session's typed ``StreamPlan``.
    The historical keyword surface (cfg + mode + padding kwargs) is kept
    as a deprecated shim that builds the equivalent plan/session.
    """

    def __init__(self, cfg: Optional[DGNNConfig] = None,
                 feat_table: Optional[np.ndarray] = None,
                 n_global: Optional[int] = None,
                 mode: Optional[str] = None,
                 n_pad: int = 640, e_pad: int = 4096, k_max: int = 64,
                 queue_depth: int = 2,
                 buckets: Optional[tuple] = None,
                 stream_chunk: int = 8,
                 promote_buckets: Optional[float] = None,
                 promotion_guard: str = "static",
                 scheduler: str = "rounds",
                 state_pool_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None, *,
                 plan=None, session=None, express=None):
        from repro import api

        if session is None:
            warnings.warn(
                "the SnapshotServer keyword surface (cfg + mode + padding "
                "kwargs) is deprecated: build a typed plan and pass "
                "SnapshotServer(session=BoosterSession(cfg, plan, ...))",
                DeprecationWarning, stacklevel=2)
            if cfg is None:
                raise ValueError("SnapshotServer needs a BoosterSession "
                                 "(session=) or a DGNNConfig")
            if n_global is None:
                raise ValueError("SnapshotServer needs n_global (the "
                                 "global node-store size) on the config "
                                 "surface — an undersized default would "
                                 "silently scatter-drop high node ids")
            if plan is None:
                # deprecated keyword surface -> the equivalent typed plan
                plan = api.plan(
                    cfg, level=mode if mode is not None else cfg.dataflow,
                    n_pad=n_pad, e_pad=e_pad, k_max=k_max,
                    queue_depth=queue_depth, buckets=buckets,
                    stream_chunk=stream_chunk,
                    promote_buckets=promote_buckets,
                    promotion_guard=promotion_guard,
                    scheduler=scheduler,
                    state_pool_pages=state_pool_pages,
                    prefill_chunk=prefill_chunk)
            session = api.BoosterSession(cfg, plan, n_global=n_global,
                                         feat_table=feat_table)
        self.session = session
        self.plan = session.plan
        if self.plan.device.n_devices > 1:
            # the serve loops pick their own launch batch sizes (B=1
            # chunks, pow2 tenant rounds), which need not divide
            # n_devices — reject up front instead of crashing mid-serve.
            raise ValueError(
                "DeviceSpec sharding is a batched-launch capability "
                "(BoosterSession.run_batched / api.run_arrays); the "
                "serving engine does not shard its launches")
        self.cfg = session.cfg
        self.model = session.model
        self.feat_table = (feat_table if feat_table is not None
                           else session.feat_table)
        if self.feat_table is None:
            raise ValueError("SnapshotServer needs the global feat_table")
        # plan-derived knobs (kept as attributes for callers/tests)
        self.mode = self.plan.level
        self.n_pad, self.e_pad = self.plan.n_pad, self.plan.e_pad
        self.k_max = self.plan.k_max
        self.buckets = self.plan.buckets
        self.stream_chunk = self.plan.stream_chunk
        self.queue_depth = self.plan.queue_depth
        self.promote_buckets = self.plan.promote_buckets
        self.scheduler = self.plan.scheduler
        self.state_pool_pages = self.plan.state_pool_pages
        self.prefill_chunk = self.plan.prefill_chunk
        self._bucket_ms: Optional[dict] = None  # measured-guard calibration
        self._calib_error: Optional[str] = None  # fallback-to-static reason
        self._policy = SupervisionPolicy.from_plan(self.plan)
        self._injector = (self.plan.fault_plan.injector()
                          if self.plan.fault_plan is not None else None)
        self._fault_exempt = False   # calibration launches skip probes
        self._launch_ctx: tuple = ()  # live sids of the in-flight launch
        self._warmed: set = set()    # launch signatures past first compile
        self._trace = RunTrace()     # spans and stamps of the current run
        self._step = jax.jit(
            lambda p, s, snap: self.model.step(p, s, snap, mode=self.mode))
        # every v3 serve launch takes the batched ragged-T entry: chunk
        # tails and batch-padding rows are dead ``lengths`` slots masked
        # in-launch, not host-built empty snapshots. The force-ref twin is
        # the degradation ladder's oracle rung (pure-XLA production path).
        self._stream_step_batched = jax.jit(
            lambda p, s, sBT, lens: self.model.step_stream_batched(
                p, s, sBT, tn=self.plan.tn, td=self.plan.td, lengths=lens,
                state_residency=self.plan.state_residency,
                buffer_depth=self.plan.buffer_depth))
        self._stream_step_batched_ref = jax.jit(
            lambda p, s, sBT, lens: self.model.step_stream_batched(
                p, s, sBT, tn=self.plan.tn, td=self.plan.td, lengths=lens,
                state_residency=self.plan.state_residency,
                buffer_depth=self.plan.buffer_depth,
                force_ref=True))
        # ------------------------------------------------ express lane ----
        # a second, STATIC-family BoosterSession: its tenants are
        # stateless, so their snapshots co-batch — each one an independent
        # T=1 slot of a dedicated launch with no checkpoint/rollback
        # around it (see ``run_multi``'s ``express_streams``).
        self.express = express
        if express is not None:
            if express.plan.temporal != "static":
                raise ValueError(
                    "express= takes a BoosterSession of a STATIC-temporal "
                    f"family; {express.model.stream_family!r} declares "
                    f"temporal={express.plan.temporal!r}")
            if express.plan.level != "v3":
                raise ValueError("the express lane is a stream-engine "
                                 "path: the express plan must be level "
                                 f"'v3', got {express.plan.level!r}")
            if express.plan.device.n_devices > 1:
                raise ValueError("the express lane does not shard its "
                                 "launches (see the session sharding note "
                                 "above)")
            if express.plan.buckets is not None:
                raise ValueError(
                    "the express lane co-batches every static slot into "
                    "ONE shape; give the express plan a fixed bucket "
                    "(buckets=None)")
            self._express_feat = (express.feat_table
                                  if express.feat_table is not None
                                  else self.feat_table)
            xp = express.plan
            self._express_step = jax.jit(
                lambda p, sBT, lens: express.model.step_stream_batched(
                    p, {}, sBT, tn=xp.tn, td=xp.td, lengths=lens)[1])

    def init(self, rng):
        return self.session.init(rng)

    # ------------------------------------------------- fault injection ----

    def _probe(self, site: str, tenant=None) -> None:
        """Host-side fault-site probe (preprocess/bucket/evolve sites;
        launch-site probes fire inside the traced program via the
        kernels/ops fault hook).

        Deliberately does NOT consult ``_fault_exempt``: calibration never
        reaches a host site, but it flips that flag on the device loop
        while producer threads run host probes concurrently — gating here
        would let a calibration window swallow a concurrent tenant's
        preprocess/bucket occurrence counts (the stats/occurrence-window
        leak the calibration-isolation regression test pins). Only
        ``_launch_probe`` is gated, and only calibration launches run
        under the flag, on the same thread that sets it."""
        if self._injector is not None:
            self._injector.probe(
                site, tenants=() if tenant is None else (tenant,))

    def _launch_probe(self, *, family, batched, force_ref) -> None:
        """The kernels/ops fault hook: fires at RUN time inside every
        stream-engine dispatch, with the engine supplying the live-tenant
        context of the in-flight launch."""
        del family, batched  # scope is judged on live tenants, not shape
        if self._injector is None or self._fault_exempt:
            return
        sids = self._launch_ctx
        self._injector.probe("launch", tenants=sids, n_live=len(sids),
                             force_ref=force_ref)

    @contextmanager
    def _fault_window(self):
        """Install the ops-layer launch hook for the duration of a serve
        run (only when the fault plan addresses the launch site), and
        restore the previous hook on every exit path."""
        if (self._injector is None
                or "launch" not in self.plan.fault_plan.sites()):
            yield
            return
        prev = kops.set_fault_hook(self._launch_probe)
        try:
            yield
        finally:
            kops.set_fault_hook(prev)

    def _attribution(self, exc: BaseException) -> BaseException:
        """Map a launch exception to its root fault: an injected fault
        crosses the XLA callback boundary rewrapped, so ask the injector
        what fired; otherwise the exception speaks for itself."""
        if self._injector is not None:
            fired = self._injector.take_fired()
            if fired is not None:
                return fired
        return exc

    # ------------------------------------------------------ host thread ----

    def _prepare(self, snap: COOSnapshot, tenant=SOLO_SID, *, feat=None,
                 pad: Optional[tuple] = None, defer: bool = False,
                 slabs: Optional[_ChunkSlabs] = None) -> tuple:
        """Host prep of one snapshot: validate, renumber + normalize,
        choose the bucket, pad. Returns ``(snapshot, (n, e, k) dims)``.

        Shapes must be static so the jitted step never recompiles (the
        "snapshot fits in BRAM" contract; overflow = the bucket chooser
        picked wrong and should raise). With ``buckets`` the shapes are
        static PER BUCKET: one compiled step per bucket in the jit cache.
        ``pad`` fixes the bucket (the express lane's); ``defer`` leaves a
        bucketed snapshot unpadded, for the device loop to pad once the
        chunk's bucket — the max over its members — is known. Where the
        bucket is fixed for the whole stream, the snapshot is padded into
        the next row of the producer's ``slabs``."""
        feat = self.feat_table if feat is None else feat
        self._probe("preprocess", tenant=tenant)
        validate_snapshot(snap, feat.shape[0], tenant=tenant)
        ls = renumber_and_normalize(snap)
        dims = (ls.n_nodes, ls.src.shape[0], max_in_degree(ls))
        out = None
        if pad is None and self.buckets is not None:
            self._probe("bucket", tenant=tenant)
            pad = choose_bucket(*dims, self.buckets)  # fails fast on no fit
            if defer:
                return ls, dims
        else:
            # fixed bucket known up front: pad here so the host prep fully
            # overlaps device work
            pad = pad or (self.n_pad, self.e_pad, self.k_max)
            if slabs is not None:
                out = slabs.row(*pad, feat.shape[1], ls.edge_feat.shape[1])
        return pad_snapshot(ls, feat, *pad, out=out), dims

    def _start_producer(self, sid, snaps: Iterable[COOSnapshot],
                        q: queue.Queue, stop: threading.Event, prepare,
                        name: str) -> threading.Thread:
        """Start one host prep thread: ``prepare(snapshot, sid, slabs)``
        each snapshot of ``snaps`` under a ``serve.prep`` span (``slabs``:
        the thread's ``_ChunkSlabs``), stamp its
        arrival and readiness, and put the result on ``q`` in stream
        order; then ``None`` at end-of-stream — or the ``BaseException``
        the producer failed with (validation, no-fit bucket, injected
        fault), which the device loop turns into a quarantine/raise per
        policy. Puts wake on ``stop`` so shutdown never blocks on a full
        queue."""
        trace = self._trace
        arrive = trace.arrive_ms.setdefault(sid, [])
        ready = trace.ready_ms.setdefault(sid, [])

        def put(item):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            slabs = _ChunkSlabs(self.stream_chunk)
            try:
                for s in snaps:
                    t_arrive = trace.now_ms()
                    c0 = time.thread_time()
                    with trace.span("serve.prep", tenant=sid) as sp:
                        item = prepare(s, sid, slabs)
                    trace.prep_cpu_ms.append((time.thread_time() - c0) * 1e3)
                    trace.prep_ms.append(sp.ms)
                    arrive.append(t_arrive)
                    ready.append(trace.now_ms())
                    if not put(item):
                        return
                put(None)
            except BaseException as exc:  # propagate, don't hang the consumer
                put(exc)

        th = threading.Thread(target=producer, daemon=True, name=name)
        with trace.span("serve.spawn", tenant=sid):
            th.start()  # waits for the new thread to run, GIL included
        return th

    # -------------------------------------------------------- shutdown ----

    @staticmethod
    def _drain(q: queue.Queue) -> None:
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass

    def _shutdown(self, stop: threading.Event, queues: list,
                  threads: list) -> None:
        """Deterministic producer shutdown, run on EVERY exit path: signal
        stop, then drain-join until every producer thread has exited (a
        producer blocked on a full queue wakes on the drain; one blocked
        on the stop-aware put wakes on the event). A thread still alive
        past the deadline is stuck in user iterator code — warned about,
        since Python offers no way to kill it."""
        with self._trace.span("serve.shutdown"):
            stop.set()
            deadline = time.perf_counter() + _SHUTDOWN_DEADLINE_S
            alive = [th for th in threads if th.is_alive()]
            while alive and time.perf_counter() < deadline:
                for q in queues:
                    self._drain(q)
                for th in alive:
                    th.join(timeout=0.05)
                alive = [th for th in alive if th.is_alive()]
        for th in alive:
            warnings.warn(f"serve producer thread {th.name!r} did not exit "
                          "within the shutdown deadline (stuck in the "
                          "stream iterator?)", RuntimeWarning)

    # ------------------------------------------------------ device loop ----

    def _use_stream(self) -> bool:
        # mode v3 requires the model's family to be registered with the
        # stream engine (all three are). Raising here keeps an
        # unregistered family LOUD instead of silently degrading to the
        # per-snapshot loop — the silent-fallback class PR 3 deleted.
        from repro.kernels.stream_fused import REGISTRY

        if self.mode != "v3":
            return False
        if self.model.stream_family not in REGISTRY:
            raise KeyError(
                f"plan level 'v3' but family {self.model.stream_family!r} "
                f"has no stream-engine cell spec; registered: "
                f"{sorted(REGISTRY)}")
        return True

    def _launch_ragged(self, params, states_B, batch_BT,
                       lengths: np.ndarray, force_ref: bool = False):
        """ONE batched ragged-T stream launch: ``batch_BT`` is the
        (B, T, ...) ``stack_streams`` of equal-shape chunks, ``lengths``
        their true live lengths (0 = pure batch-padding row). The dead
        slots are masked in-launch by the plan's ragged capability.
        ``force_ref`` routes to the jitted oracle twin (degraded-mode
        rung)."""
        fn = (self._stream_step_batched_ref if force_ref
              else self._stream_step_batched)
        return fn(params, states_B, batch_BT,
                  jnp.asarray(lengths, jnp.int32))

    # -------------------------------------------------- supervised launch ----

    def _count_launch(self, ctr: dict, family: str) -> int:
        """Count one launch attempt; returns its index in the run (the
        ``launch`` argument of its spans)."""
        ctr["launches"] += 1
        bf = ctr.setdefault("by_family", {})
        bf[family] = bf.get(family, 0) + 1
        return ctr["launches"]

    def _stage_group(self, params, states: dict, group: list, ctr: dict,
                     force_ref: bool = False, launch: int = 0) -> tuple:
        """Launch one batched V3 group WITHOUT committing anything: build
        the (B, T) batch, run it, and return the staged per-tenant results
        ``(staged_states, staged_outs, dt_per_snapshot_ms, live, padded)``.
        Commit/rollback is the supervised runner's job, so a failure here
        (or after, in the commit phase) leaves tenant state untouched.

        ``group`` is [(sid, [LocalSnapshot | PaddedSnapshot, ...],
        bucket), ...]. Each stream's chunk is padded to the shared bucket
        and stacked to a (B, T, ...) batch with the per-stream states
        alongside; T is the common power-of-two target and the BATCH axis
        is pow2-padded too, so the jit cache stays bounded at log2 sizes
        per (bucket, T) instead of compiling one program per distinct
        client count as tenants join and finish. Raggedness is carried by
        ``lengths`` (stream b live for lengths[b] steps, padding rows live
        for 0) and masked in-launch — no host-built empty snapshots. Row b
        of the launch result is that stream's output in stream order.

        The plan's ``launch_timeout_ms`` deadline is enforced on
        completion (JAX launches cannot be cancelled): an overdue result
        raises ``LaunchTimeout`` and is DISCARDED by the caller. The first
        launch of each (bucket, T, B, path) signature is exempt — it pays
        one-time compilation.

        The phases run under the spans ``serve.stage`` (everything before
        the timed launch), ``serve.stack_batch``, ``serve.dispatch`` and
        ``serve.device_wait`` (the timed launch wall) and
        ``serve.unstage``, each with the ``launch`` index. ``ctr`` counts
        the chunks staged and those whose time stack is a view of a
        producer's chunk slab (``staged``, ``in_place``).
        """
        span = self._trace.span
        bucket = group[0][2]
        real_lens = [len(chunk) for _, chunk, _ in group]
        target = pow2_target(max(real_lens), cap=self.stream_chunk)
        b_real = len(group)
        b_target = pow2_target(b_real)
        with span("serve.stage", launch=launch, B=b_target, T=target):
            per_stream = []
            in_place = 0
            for _, chunk, _ in group:
                # fixed-bucket items arrive pre-padded from the producer
                # thread (host-prep overlap); bucketed items pad here, once
                # the chunk bucket is known.
                padded = [ls if isinstance(ls, PaddedSnapshot)
                          else pad_snapshot(ls, self.feat_table, *bucket)
                          for ls in chunk]
                # ragged T: tail slots repeat the last snapshot — dead
                # ``lengths`` slots, masked in-launch, content irrelevant
                padded = padded + [padded[-1]] * (target - len(padded))
                stacked = stack_time(padded)
                # a view of the slab shares its rows' base (a copy has none);
                # no numpy call here, as each one would give up the GIL
                in_place += all(a.base is not None and a.base is b.base
                                for a, b in zip(jax.tree.leaves(stacked),
                                                jax.tree.leaves(padded[0])))
                per_stream.append(stacked)
            ctr["staged"] = ctr.get("staged", 0) + b_real
            ctr["in_place"] = ctr.get("in_place", 0) + in_place
            # batch-axis padding = length-0 streams (results discarded)
            per_stream.extend([per_stream[0]] * (b_target - b_real))
            lengths = np.asarray(real_lens + [0] * (b_target - b_real),
                                 np.int32)
            rows = [states[sid] for sid, _, _ in group]
            if b_target > b_real:
                zero_state = jax.tree.map(jnp.zeros_like, rows[0])
                rows += [zero_state] * (b_target - b_real)
            states_B = _stack_states(tuple(rows))
        key = (bucket, target, b_target, force_ref)
        warmed = key in self._warmed
        self._launch_ctx = tuple(sid for sid, _, _ in group)
        try:
            t0 = time.perf_counter()
            with span("serve.stack_batch", t0, launch=launch) as sp:
                batch_BT = stack_streams(per_stream)
            with span("serve.dispatch", sp.t1, launch=launch) as sp:
                states_B, out_BT = self._launch_ragged(
                    params, states_B, batch_BT, lengths, force_ref=force_ref)
            with span("serve.device_wait", sp.t1, launch=launch):
                jax.block_until_ready(out_BT)
            dt_ms = (time.perf_counter() - t0) * 1e3
        finally:
            self._launch_ctx = ()
        self._warmed.add(key)
        timeout = self._policy.timeout_ms
        if timeout is not None and warmed and dt_ms > timeout:
            raise LaunchTimeout(
                f"launch took {dt_ms:.1f}ms > launch_timeout_ms={timeout}"
                f" (bucket={bucket}, B={b_target}, T={target}); result "
                "discarded", site="launch")
        with span("serve.unstage", launch=launch):
            out_np = np.asarray(out_BT)
            staged_states = {
                sid: jax.tree.map(lambda a, b=b: a[b], states_B)
                for b, (sid, _, _) in enumerate(group)}
            staged_outs = {sid: [out_np[b, t] for t in range(real_lens[b])]
                           for b, (sid, _, _) in enumerate(group)}
        live = sum(real_lens)
        padded_slots = b_target * target - live
        return staged_states, staged_outs, dt_ms / live, live, padded_slots

    def _commit_group(self, states: dict, group: list, staged: tuple,
                      outs: dict, lat: list, ctr: dict, sup: TenantSupervisor,
                      launch_ms: float, degraded: bool = False) -> None:
        """Commit one staged group: the ``evolve`` fault site sits inside
        the state-commit loop, so an injected (or real) mid-commit failure
        leaves ``states`` partially written — exactly what the
        supervisor's checkpoint/rollback must undo for the replay to
        evolve state exactly once per served snapshot. ``launch_ms`` is
        when the launch attempt began (ms since run start)."""
        staged_states, staged_outs, dt, live, padded_slots = staged
        for sid, _, _ in group:
            self._probe("evolve", tenant=sid)
            states[sid] = staged_states[sid]
        # commit wall-clock (ms since run start) recorded per snapshot —
        # only after the whole evolve loop, so a rolled-back commit never
        # stamps timestamps for outputs it did not serve
        trace = self._trace
        now_ms = trace.now_ms()
        for sid, chunk, _ in group:
            outs[sid].extend(staged_outs[sid])
            trace.commit_ms.setdefault(sid, []).extend([now_ms] * len(chunk))
            trace.launch_start_ms.setdefault(sid, []).extend(
                [launch_ms] * len(chunk))
            lat.extend([dt] * len(chunk))
            if degraded:
                sup.note_degraded(sid)
        ctr["live"] += live
        ctr["padded"] += padded_slots
        if degraded:
            ctr["degraded"] += 1

    def _attempt(self, params, states: dict, members: list, outs: dict,
                 lat: list, ctr: dict, sup: TenantSupervisor,
                 force_ref: bool = False, degraded: bool = False):
        """One launch attempt of ``members`` under a ``serve.launch`` span:
        checkpoint their state, stage the launch, commit it; on a failure
        roll the state back. Returns None when the attempt served, else the
        attributed error. The static temporal contract has NOTHING to
        checkpoint — tenant state is empty and never advances — so the
        express-lane promise (no checkpoint/rollback overhead around
        stateless launches) holds for a static-family session on the
        regular path too."""
        span = self._trace.span
        sids = [sid for sid, _, _ in members]
        k = self._count_launch(ctr, self.model.stream_family)
        with span("serve.launch", launch=k, B=len(members)) as attempt:
            ckpt = None
            if self.plan.temporal != "static":
                with span("serve.checkpoint", launch=k):
                    ckpt = sup.checkpoint(states, sids)
            try:
                staged = self._stage_group(params, states, members, ctr,
                                           force_ref=force_ref, launch=k)
                with span("serve.commit", launch=k):
                    self._commit_group(states, members, staged, outs, lat,
                                       ctr, sup, launch_ms=attempt.start_ms,
                                       degraded=degraded)
                return None
            except Exception as exc:
                err = self._attribution(exc)
                if isinstance(err, LaunchTimeout):
                    ctr["timeouts"] += 1
                if ckpt is not None:
                    with span("serve.checkpoint", launch=k):
                        sup.rollback(states, ckpt)
                return err

    def _degrade_group(self, params, states: dict, members: list,
                       outs: dict, lat: list, ctr: dict,
                       sup: TenantSupervisor, cause: BaseException) -> None:
        """The degradation ladder's lower rungs, per member: a solo (B=1)
        v3 launch isolates the batch from a poisoned co-tenant; if the
        kernel path itself is the fault, the pure-XLA oracle (force-ref
        gate) serves correct-but-slower results. A member that fails every
        rung is quarantined (isolate) or raises (strict) with the LAST
        error as cause."""
        for member in members:
            err = cause
            for force_ref in (False, True):
                failed = self._attempt(params, states, [member], outs, lat,
                                       ctr, sup, force_ref=force_ref,
                                       degraded=True)
                if failed is None:
                    break
                err = failed
            else:
                sup.quarantine(member[0], err,
                               site=getattr(err, "site", "launch"))

    def _run_group_supervised(self, params, states: dict, group: list,
                              outs: dict, lat: list, ctr: dict,
                              sup: TenantSupervisor) -> None:
        """One batched V3 group under the supervision contract:

          1. checkpoint every member's recurrent state;
          2. stage the batched launch + commit (the happy path);
          3. on failure: roll back, then retry the SAME group up to
             ``max_retries`` times with exponential backoff (a transient
             fault is survived in place, replaying from the checkpoint);
          4. retries exhausted + fault attributable to one member: that
             tenant is quarantined and the remaining members are
             transparently retried without it;
          5. retries exhausted + unattributable: walk the degradation
             ladder (plan ``degrade=True``), else quarantine the whole
             group (isolate) / raise (strict).
        """
        members = [m for m in group if sup.ok(m[0])]
        attempt = 0
        while members:
            sids = [sid for sid, _, _ in members]
            err = self._attempt(params, states, members, outs, lat, ctr, sup)
            if err is None:
                return
            attempt += 1
            if attempt <= self._policy.max_retries:
                sup.note_retry(sids, attempt)
                continue
            tenant = getattr(err, "tenant", None)
            if tenant is not None and tenant in sids:
                # persistent fault pinned to one member: quarantine it,
                # retry the healthy co-batch without it
                sup.quarantine(tenant, err,
                               site=getattr(err, "site", "launch"))
                members = [m for m in members if m[0] != tenant]
                attempt = 0
                continue
            if self._policy.degrade:
                self._degrade_group(params, states, members, outs, lat,
                                    ctr, sup, err)
                return
            # no ladder: the whole group fails together
            for sid in sids:
                sup.quarantine(sid, err,
                               site=getattr(err, "site", "launch"))
            return

    def _run_chunk(self, params, states: dict, chunk: list, outs: dict,
                   lat: list, ctr: dict, sup: TenantSupervisor) -> None:
        """Feed one same-bucket single-tenant chunk to the time-fused
        stream kernel (a B=1 supervised launch).

        Short flushes (tail of the stream, or a bucket change on a
        bucket-alternating stream) pad T up to the next power of two, not
        all the way to ``stream_chunk`` — at most 2x dead slots while the
        jit cache stays bounded at log2(stream_chunk)+1 chunk lengths per
        bucket. The tail repeats the last snapshot; its content is
        ignored (masked by ``lengths``).
        """
        bucket = (chunk[0].n_pad, chunk[0].e_pad, chunk[0].k_max)
        self._run_group_supervised(params, states,
                                   [(SOLO_SID, chunk, bucket)], outs, lat,
                                   ctr, sup)

    def _step_one(self, params, state, ps: PaddedSnapshot,
                  lat: list) -> tuple:
        """One per-snapshot step of the non-v3 engine modes: dispatch,
        wait, output to host. Returns ``(new_state, output)``."""
        span = self._trace.span
        t0 = time.perf_counter()
        with span("serve.dispatch"):
            state, out = self._step(params, state, ps)
        with span("serve.device_wait"):
            jax.block_until_ready(out)
        lat.append((time.perf_counter() - t0) * 1e3)
        with span("serve.unstage"):
            return state, np.asarray(out)

    def _make_stats(self, lat, ctr,
                    sup: Optional[TenantSupervisor]) -> ServeStats:
        """The run's ``ServeStats``; its total wall ends now."""
        trace = self._trace
        total = trace.now_ms()
        totals = sup.totals() if sup is not None else {}
        return ServeStats(
            lat, list(trace.prep_ms), total,
            live_snapshots=ctr["live"], padded_snapshots=ctr["padded"],
            promoted_chunks=ctr["promoted"], launches=ctr["launches"],
            express_launches=ctr.get("express", 0),
            launches_by_family=dict(ctr.get("by_family", {})),
            retries=totals.get("retries", 0),
            rollbacks=totals.get("rollbacks", 0),
            degraded_launches=totals.get("degraded_launches", 0),
            timeouts=ctr.get("timeouts", 0),
            tenants=dict(sup.results) if sup is not None else {},
            calibration_fallback=self._calib_error,
            ticks=ctr.get("ticks", 0),
            prefill_chunks=ctr.get("prefill", 0),
            evictions=totals.get("evictions", 0),
            recoveries=totals.get("recoveries", 0),
            commit_ms=dict(trace.commit_ms),
            **trace.stamps(),
            phase_ms=dict(trace.phase_ms),
            phase_n=dict(trace.phase_n),
            preprocess_cpu_ms=list(trace.prep_cpu_ms),
            staged_chunks=ctr.get("staged", 0),
            staged_in_place=ctr.get("in_place", 0))

    def run(self, params, state, snaps: Iterable[COOSnapshot]) -> tuple:
        """Returns (final_state, outputs list, ServeStats).

        Single-tenant edition of the supervision contract: the stream is
        supervised under the sid ``"stream"`` — with the default strict
        policy every failure raises (after a clean shutdown); with plan
        ``supervision="isolate"`` a terminal failure stops the stream and
        returns the partial outputs with the error recorded in
        ``stats.tenants["stream"]``.
        """
        # the v3 device loop consumes ``stream_chunk`` snapshots per kernel
        # launch; a queue_depth-sized queue would stall the producer at 2
        # staged snapshots while a whole chunk runs, killing the §IV-D
        # host/device overlap — size for a full chunk ahead, like run_multi.
        # Per-snapshot modes keep the caller's queue_depth memory bound.
        depth = (max(self.queue_depth, self.stream_chunk)
                 if self._use_stream() else self.queue_depth)
        q: queue.Queue = queue.Queue(maxsize=depth)
        stop = threading.Event()
        self._trace = RunTrace()
        span = self._trace.span
        th = self._start_producer(SOLO_SID, snaps, q, stop,
                                  lambda s, sid, slabs: self._prepare(
                                      s, sid, slabs=slabs)[0],
                                  name=f"dgnn-serve-producer-{SOLO_SID}")
        outs: list = []
        lat: list = []
        ctr = {"live": 0, "padded": 0, "promoted": 0, "launches": 0,
               "timeouts": 0, "degraded": 0}
        sup = TenantSupervisor([SOLO_SID], self._policy,
                               outputs={SOLO_SID: outs})
        states = {SOLO_SID: state}
        outs_d = {SOLO_SID: outs}
        use_stream = self._use_stream()
        chunk: list = []
        try:
            with self._fault_window():
                while sup.ok(SOLO_SID):
                    with span("serve.wait_producers"):
                        ps = q.get()
                    if ps is None:
                        break
                    if isinstance(ps, BaseException):
                        # e.g. validation / no-fit bucket: strict raises,
                        # isolate records and stops the stream
                        sup.quarantine(SOLO_SID, ps,
                                       site=getattr(ps, "site", None))
                        break
                    if not use_stream:
                        ckpt = sup.checkpoint(states, [SOLO_SID])
                        try:
                            states[SOLO_SID], out = self._step_one(
                                params, states[SOLO_SID], ps, lat)
                            outs.append(out)
                        except Exception as exc:
                            sup.rollback(states, ckpt)
                            sup.quarantine(SOLO_SID, self._attribution(exc))
                        continue
                    # v3: gather same-bucket runs into fixed-T chunks
                    bucket = (ps.n_pad, ps.e_pad, ps.k_max)
                    if chunk and (chunk[0].n_pad, chunk[0].e_pad,
                                  chunk[0].k_max) != bucket:
                        self._run_chunk(params, states, chunk, outs_d, lat,
                                        ctr, sup)
                        chunk = []
                    chunk.append(ps)
                    if len(chunk) == self.stream_chunk:
                        self._run_chunk(params, states, chunk, outs_d, lat,
                                        ctr, sup)
                        chunk = []
                if chunk and sup.ok(SOLO_SID):
                    self._run_chunk(params, states, chunk, outs_d, lat, ctr,
                                    sup)
        finally:
            self._shutdown(stop, [q], [th])
        return states[SOLO_SID], outs, self._make_stats(lat, ctr, sup)

    # ------------------------------------------- multi-tenant device loop ----

    def _use_stream_batched(self) -> bool:
        # every registered family batches through the same engine kernel;
        # only the engine MODE decides (non-v3 modes keep the per-snapshot
        # device loop).
        return self._use_stream()

    def _chunk_bucket(self, dims: list) -> tuple:
        """Bucket covering a whole chunk of (n, e, k) dims (one static shape
        per chunk so the chunk can batch with same-bucket chunks of other
        streams)."""
        if self.buckets is not None:
            return choose_bucket_batch(dims, self.buckets)
        return (self.n_pad, self.e_pad, self.k_max)

    # ------------------------------------------- promotion cost guard ----

    def _calibrate_bucket_times(self, params) -> Optional[dict]:
        """Measure per-bucket stream-kernel step time with a tiny warmup:
        one empty-snapshot B=1 chunk per bucket, compiled then timed.
        The measured times replace the static ``bucket_cost`` proxy in the
        promotion guard (plan.promotion_guard == "measured"); returns None
        (static fallback) if any bucket fails to calibrate — the fallback
        is WARNED about and recorded in ``ServeStats.calibration_fallback``
        instead of failing silently.

        Calibration launches are WARM-UP, not serving: they go straight
        through ``_launch_ragged`` (never ``_stage_group``), so they touch
        neither ``ServeStats.launches``, ``per_snapshot_ms`` nor the spans'
        ``phase_ms``, and the ``_fault_exempt`` window keeps them out of
        launch-site occurrence counting — stats and fault windows on a run are identical with
        ``promotion_guard`` "measured" or "static" (pinned by the
        calibration-isolation regression test)."""
        din = self.feat_table.shape[1]
        de = self.cfg.edge_dim
        T = pow2_target(self.stream_chunk, cap=self.stream_chunk)
        times: dict = {}
        self._fault_exempt = True  # calibration is not a serve launch
        try:
            for bucket in self.buckets:
                chunk = [empty_padded(*bucket, din, de)] * T
                state = self.model.init_state(params, mode=self.mode)
                state_B = jax.tree.map(lambda a: a[None], state)
                batch = stack_streams([stack_time(chunk)])
                run = lambda: self._launch_ragged(params, state_B, batch,
                                                  np.asarray([T]))
                jax.block_until_ready(run())  # compile + warm
                t0 = time.perf_counter()
                jax.block_until_ready(run())
                times[bucket] = max((time.perf_counter() - t0) * 1e3 / T,
                                    1e-6)
        except Exception as exc:
            self._calib_error = repr(exc)
            warnings.warn(
                "measured promotion-guard calibration failed; falling back "
                f"to the static bucket_cost proxy: {exc!r}", RuntimeWarning)
            return None  # static proxy fallback
        finally:
            self._fault_exempt = False
        return times

    def _measured_cost(self, bucket: tuple) -> float:
        """Per-bucket cost under the measured guard, falling back to the
        static ``bucket_cost`` proxy PER MISS: a bucket absent from the
        calibration table (first seen after calibration ran) must not
        crash the promotion pass with a bare KeyError mid-serve — it gets
        the static estimate, and the miss is warned about and recorded in
        ``ServeStats.calibration_fallback``."""
        try:
            return self._bucket_ms[bucket]
        except KeyError:
            self._calib_error = (f"bucket {bucket!r} missing from the "
                                 "measured calibration table")
            warnings.warn(
                f"measured promotion guard: {self._calib_error}; using the "
                "static bucket_cost proxy for it", RuntimeWarning)
            return bucket_cost(bucket)

    def _promotion_cost(self, params):
        """Cost function for promote_bucket_groups: measured per-bucket
        step times when the plan asks for the adaptive guard (calibrated
        lazily, once), else the static padded-compute proxy. Measured
        lookups degrade per miss instead of raising (``_measured_cost``)."""
        if self.plan.promotion_guard != "measured":
            return bucket_cost
        if self._bucket_ms is None and self._calib_error is None:
            self._bucket_ms = self._calibrate_bucket_times(params)
        if self._bucket_ms is None:
            return bucket_cost  # calibration failed: static fallback
        return self._measured_cost

    def _spawn_producers(self, streams: dict) -> tuple:
        """Start one host preprocessing thread per tenant stream (shared
        by the round-based and continuous device loops; see
        ``_start_producer``). Returns ``(queues, stop_event, threads)``
        with the threads already running. Each queue carries
        ``(LocalSnapshot | PaddedSnapshot, dims)`` items in stream order:
        a bucketed snapshot is padded on the device loop, once the chunk
        bucket is known."""
        sids = sorted(streams)
        qs = {sid: queue.Queue(maxsize=max(self.queue_depth,
                                           self.stream_chunk))
              for sid in sids}
        stop = threading.Event()
        threads = [self._start_producer(
            sid, streams[sid], qs[sid], stop,
            lambda s, sid, slabs: self._prepare(s, sid, defer=True,
                                                slabs=slabs),
            name=f"dgnn-serve-producer-{sid}") for sid in sids]
        return qs, stop, threads

    # ---------------------------------------------------- express lane ----

    def _spawn_express_producers(self, streams: dict, stop) -> tuple:
        """Producer threads for the stateless express tenants. Always
        fixed-bucket (the lane co-batches every slot into one shape, so
        padding happens host-side, fully overlapped). Items have the
        recurrent producers' ``(payload, dims)`` shape so both feed the
        same admission code; ``stop`` is the shared shutdown event."""
        xp = self.express.plan
        pad = (xp.n_pad, xp.e_pad, xp.k_max)
        sids = sorted(streams)
        qs = {sid: queue.Queue(maxsize=max(self.queue_depth,
                                           self.stream_chunk))
              for sid in sids}
        threads = [self._start_producer(
            sid, streams[sid], qs[sid], stop,
            lambda s, sid, slabs: self._prepare(
                s, sid, feat=self._express_feat, pad=pad, slabs=slabs),
            name=f"dgnn-serve-express-{sid}") for sid in sids]
        return qs, threads

    def _run_express_group(self, params_x, group: list, outs: dict,
                           lat: list, ctr: dict,
                           sup: TenantSupervisor) -> None:
        """ONE express-lane launch: ``group`` is [(sid, [PaddedSnapshot,
        ...]), ...] — every snapshot of every member becomes an
        independent T=1 slot on the BATCH axis of a single static-family
        stream launch (B pow2-padded with dead length-0 slots). The
        tenants are stateless, so no checkpoint is taken and nothing is
        rolled back; failures follow the usual retry → attribute →
        quarantine path minus the state machinery and the degradation
        ladder (there is no cheaper rung below a stateless launch)."""
        members = [m for m in group if sup.ok(m[0])]
        attempt = 0
        trace = self._trace
        while members:
            slots = [(sid, ps) for sid, chunk in members for ps in chunk]
            sids = sorted({sid for sid, _ in slots})
            b_real = len(slots)
            b_target = pow2_target(b_real)
            key = ("express", b_target)
            warmed = key in self._warmed
            self._launch_ctx = tuple(sids)
            k = self._count_launch(ctr, self.express.model.stream_family)
            ctr["express"] = ctr.get("express", 0) + 1
            try:
                with trace.span("serve.express", launch=k,
                                B=b_target) as launch:
                    per_slot = [stack_time([ps]) for _, ps in slots]
                    per_slot.extend([per_slot[0]] * (b_target - b_real))
                    lengths = np.asarray(
                        [1] * b_real + [0] * (b_target - b_real), np.int32)
                    t0 = time.perf_counter()
                    out_BT = self._express_step(
                        params_x, stack_streams(per_slot),
                        jnp.asarray(lengths, jnp.int32))
                    jax.block_until_ready(out_BT)
                    dt_ms = (time.perf_counter() - t0) * 1e3
                    self._warmed.add(key)
                    timeout = self._policy.timeout_ms
                    if timeout is not None and warmed and dt_ms > timeout:
                        raise LaunchTimeout(
                            f"express launch took {dt_ms:.1f}ms > "
                            f"launch_timeout_ms={timeout} (B={b_target}); "
                            "result discarded", site="launch")
                    out_np = np.asarray(out_BT)
                    now_ms = trace.now_ms()
                    for b, (sid, _) in enumerate(slots):
                        outs[sid].append(out_np[b, 0])
                        trace.commit_ms.setdefault(sid, []).append(now_ms)
                        trace.launch_start_ms.setdefault(sid, []).append(
                            launch.start_ms)
                    lat.extend([dt_ms / b_real] * b_real)
                    ctr["live"] += b_real
                    ctr["padded"] += b_target - b_real
                return
            except Exception as exc:
                err = self._attribution(exc)
                if isinstance(err, LaunchTimeout):
                    ctr["timeouts"] += 1
                attempt += 1
                if attempt <= self._policy.max_retries:
                    sup.note_retry(sids, attempt)
                    continue
                tenant = getattr(err, "tenant", None)
                if tenant is not None and tenant in sids:
                    sup.quarantine(tenant, err,
                                   site=getattr(err, "site", "launch"))
                    members = [m for m in members if m[0] != tenant]
                    attempt = 0
                    continue
                for sid in sids:
                    sup.quarantine(sid, err,
                                   site=getattr(err, "site", "launch"))
                return
            finally:
                self._launch_ctx = ()

    def _pull_express(self, xqs: dict, x_active: set,
                      sup: TenantSupervisor) -> list:
        """The express round's pull: every active express tenant's next
        chunk of up to ``stream_chunk`` T=1 slots, as ``[(sid, chunk)]``.
        End-of-stream retires a tenant from ``x_active``; a producer
        failure also quarantines it per policy."""
        x_group = []
        for sid in sorted(x_active):
            chunk = []
            while len(chunk) < self.stream_chunk:
                item = xqs[sid].get()
                if item is None:
                    x_active.discard(sid)
                    break
                if isinstance(item, BaseException):
                    x_active.discard(sid)
                    chunk = []
                    sup.quarantine(sid, item,
                                   site=getattr(item, "site", None))
                    break
                chunk.append(item[0])
            if chunk:
                x_group.append((sid, chunk))
        return x_group

    def _pull_round(self, qs: dict, active: set, sup: TenantSupervisor,
                    batched: bool) -> dict:
        """The round loop's pull: the next chunk of up to ``stream_chunk``
        snapshots (one for the non-v3 loop) of every active stream, as
        ``{sid: (chunk, dims)}``. End-of-stream retires a tenant from
        ``active``. A producer-side failure (validation, no-fit bucket,
        injected fault) raises under strict; isolate quarantines THIS
        tenant — outputs stop at the last committed chunk, the round
        continues without it."""
        chunks = {}
        for sid in sorted(active):
            chunk: list = []
            dims: list = []
            while len(chunk) < self.stream_chunk:
                item = qs[sid].get()
                if item is None:
                    active.discard(sid)
                    break
                if isinstance(item, BaseException):
                    active.discard(sid)
                    chunk = []
                    sup.quarantine(sid, item,
                                   site=getattr(item, "site", None))
                    break
                chunk.append(item[0])
                dims.append(item[1])
                if not batched:
                    break  # non-v3 loop: no chunking
            if chunk:
                chunks[sid] = (chunk, dims)
        return chunks

    def _check_express_args(self, streams: dict, express_streams) -> list:
        """Validate the run_multi express arguments; returns the express
        sids (empty when the lane is unused)."""
        if not express_streams:
            return []
        if self.express is None:
            raise ValueError("express_streams= needs the express lane "
                             "configured: SnapshotServer(..., express="
                             "<static BoosterSession>)")
        clash = set(express_streams) & set(streams)
        if clash:
            raise ValueError(f"stream ids {sorted(map(repr, clash))} appear "
                             "in both streams and express_streams")
        return sorted(express_streams)

    def run_multi(self, params, states: dict, streams: dict, *,
                  express_streams: Optional[dict] = None,
                  express_params=None) -> tuple:
        """Serve many independent client streams concurrently.

        ``streams``: {stream_id: iterable of COOSnapshot}; ``states``:
        {stream_id: recurrent state} (one store per tenant — state is never
        shared across clients). Returns (states, {stream_id: [outputs]},
        ServeStats). Outputs per stream are in that stream's snapshot order.

        Two device loops, selected by ``plan.scheduler``:

        ``"rounds"`` (default): rounds of up-to-``stream_chunk`` snapshots
        per stream with a barrier between rounds; same-bucket chunks from
        different streams batch into one V3 launch.

        ``"continuous"``: iteration-level scheduling — no round barrier; a
        tick composes a fresh batch from whatever is READY, long backlogs
        are served in ``prefill_chunk``-bounded chunks interleaved with
        other tenants' steps, and per-tenant recurrent state lives in a
        paged pool (``state_pool_pages``) with LRU eviction to host and
        transparent recovery (serve/scheduler.ContinuousScheduler,
        docs/serve_scheduler.md). Outputs and final states are
        bit-identical to the round scheduler's.

        Both are supervised per the plan's fault-isolation policy (see the
        module docstring): with ``supervision="isolate"`` a failing tenant
        is quarantined — its error lands in ``stats.tenants[sid]``, its
        outputs stop at the last committed chunk — and the surviving
        tenants are unaffected; the strict default re-raises the first
        failure after a clean shutdown.

        EXPRESS LANE: with the server built over a second STATIC-family
        session (``SnapshotServer(..., express=<static BoosterSession>)``),
        ``express_streams`` ({sid: iterable of COOSnapshot}, disjoint from
        ``streams``) are served through it with ``express_params``. Static
        tenants are stateless — every snapshot is an independent T=1 slot —
        so each round/tick co-batches ALL ready express snapshots into one
        dedicated launch with no checkpoint/rollback around it, counted in
        ``ServeStats.express_launches`` / ``launches_by_family``. Express
        outputs land in the same outputs dict, in stream order.
        """
        x_sids = self._check_express_args(streams, express_streams)
        if self.plan.scheduler == "continuous":
            from repro.serve.scheduler import ContinuousScheduler

            return ContinuousScheduler(self).run(
                params, states, streams, express_streams=express_streams,
                express_params=express_params)
        return self._run_multi_rounds(params, states, streams,
                                      express_streams if x_sids else None,
                                      express_params)

    def _run_multi_rounds(self, params, states: dict, streams: dict,
                          express_streams: Optional[dict] = None,
                          express_params=None) -> tuple:
        """The round-based multi-tenant device loop (plan.scheduler ==
        "rounds"); see ``run_multi`` for the contract."""
        sids = sorted(streams)
        x_sids = sorted(express_streams or {})
        self._trace = RunTrace()
        span = self._trace.span
        qs, stop, threads = self._spawn_producers(streams)
        xqs: dict = {}
        if x_sids:
            xqs, x_threads = self._spawn_express_producers(
                express_streams, stop)
            threads = threads + x_threads
        outs: dict = {sid: [] for sid in sids + x_sids}
        lat: list = []
        ctr = {"live": 0, "padded": 0, "promoted": 0, "launches": 0,
               "timeouts": 0, "degraded": 0}
        sup = TenantSupervisor(sids + x_sids, self._policy, outputs=outs)
        active = set(sids)
        x_active = set(x_sids)
        batched = self._use_stream_batched()
        try:
            with self._fault_window():
                while active or x_active:
                    # express round: every express tenant's next chunk of
                    # T=1 slots, co-batched into ONE stateless launch
                    x_group: list = []
                    if x_active:
                        with span("serve.wait_producers", express=True):
                            x_group = self._pull_express(xqs, x_active, sup)
                    if x_group:
                        self._run_express_group(express_params, x_group,
                                                outs, lat, ctr, sup)
                        x_active -= set(sup.quarantined)
                    if not active:
                        continue
                    # one round: pull the next chunk of every active stream
                    with span("serve.wait_producers"):
                        chunks = self._pull_round(qs, active, sup, batched)
                    if not chunks:
                        continue
                    if not batched:
                        # non-v3 engine modes: round-robin per-snapshot
                        # stepping, checkpointed per snapshot
                        for sid, (chunk, dims) in sorted(chunks.items()):
                            if not sup.ok(sid):
                                continue
                            ckpt = sup.checkpoint(states, [sid])
                            try:
                                for ls, d in zip(chunk, dims):
                                    ps = (ls if isinstance(ls, PaddedSnapshot)
                                          else pad_snapshot(
                                              ls, self.feat_table,
                                              *self._chunk_bucket([d])))
                                    ckpt = sup.checkpoint(states, [sid])
                                    states[sid], out = self._step_one(
                                        params, states[sid], ps, lat)
                                    outs[sid].append(out)
                            except Exception as exc:
                                sup.rollback(states, ckpt)
                                sup.quarantine(sid, self._attribution(exc))
                                active.discard(sid)
                        continue
                    # group same-bucket chunks across streams -> one
                    # supervised launch each
                    groups: dict = {}
                    for sid, (chunk, dims) in sorted(chunks.items()):
                        bucket = self._chunk_bucket(dims)
                        groups.setdefault(bucket, []).append(
                            (sid, chunk, bucket))
                    if (self.promote_buckets is not None
                            and self.buckets is not None):
                        # cross-bucket batching: promote smaller-bucket
                        # chunks into the next-larger in-flight bucket
                        # (guarded by the per-bucket cost ratio — measured
                        # step times under the plan's adaptive guard, else
                        # the static padded-compute proxy) so they join its
                        # launch instead of paying their own dispatch.
                        before = {b: len(m) for b, m in groups.items()}
                        groups = promote_bucket_groups(
                            groups, self.buckets, self.promote_buckets,
                            cost=self._promotion_cost(params))
                        ctr["promoted"] += sum(
                            len(m) - before.get(b, 0)
                            for b, m in groups.items())
                    for bucket in sorted(groups):
                        self._run_group_supervised(params, states,
                                                   groups[bucket], outs,
                                                   lat, ctr, sup)
                    # tenants quarantined by the launch path stop being
                    # scheduled (their producers are drained at shutdown)
                    active -= set(sup.quarantined)
        finally:
            self._shutdown(stop, list(qs.values()) + list(xqs.values()),
                           threads)
        return states, outs, self._make_stats(lat, ctr, sup)
