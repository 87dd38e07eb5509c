"""Continuous-batching device loop for the multi-tenant serve engine.

The round-based ``run_multi`` loop has a BARRIER between rounds: every
active tenant must deliver its next chunk (or EOF) before any launch
happens, so one tenant with a long snapshot backlog — a client replaying
history, a reconnect after downtime — stalls every incremental tenant
behind its full prefill, and a tenant whose producer is slow stalls the
round outright.

This module replaces the barrier with ITERATION-LEVEL scheduling (the
vLLM/sglang continuous-batching idea, at snapshot-stream granularity):

  tick loop    Each engine tick composes a fresh ragged StreamPlan batch
               from whatever snapshots are READY — no waiting for
               stragglers; a tenant joins a launch with a 1-snapshot chunk
               if that is all it has. Chunk-boundary invariance makes this
               safe: serving a stream in chunks of ANY lengths is
               bit-identical to any other chunking (pinned by the
               differential tests), so tick composition is a pure
               scheduling decision, never a numerics one.

  chunked      A backlogged tenant (more than ``stream_chunk`` snapshots
  prefill      queued) is served at most ``plan.prefill_chunk`` snapshots
               per tick instead of a full chunk, so its backlog drains
               INTERLEAVED with other tenants' incremental steps rather
               than monopolizing launches — bounded per-tick share, lower
               p99 for everyone else.

  paged state  Per-tenant recurrent state lives in a fixed-size paged pool
  pool         (``plan.state_pool_pages`` device-resident tenants,
               serve/state_pool.TenantStatePool). The tick working set is
               capped at the pool size; least-recently-scheduled tenants
               outside it are spilled to host via the supervisor's
               checkpoint machinery and transparently recovered when next
               scheduled — f32 round-trips bit-exactly, so eviction is
               invisible in the outputs.

  fairness     Ready tenants are served least-recently-scheduled first, so
               under pool pressure the working set round-robins instead of
               starving whoever sorts last.

Everything below the tick — bucketing, promotion, the supervised
stage/commit launch with checkpoint/rollback, retries, quarantine, the
degradation ladder — is the SAME engine code the round loop uses
(``SnapshotServer._run_group_supervised`` and friends), so the fault
contract of docs/serve_robustness.md holds unchanged under this
scheduler; the chaos lane pins it.
"""
from __future__ import annotations

import queue
import time
from collections import deque

from repro.graph.padding import promote_bucket_groups
from repro.serve.spans import RunTrace
from repro.serve.state_pool import TenantStatePool
from repro.serve.supervision import TenantSupervisor

# idle backoff while every backlog is empty but producers are still
# running (host prep slower than the device loop)
_IDLE_SLEEP_S = 5e-4


class ContinuousScheduler:
    """One continuous-batching serve run over a ``SnapshotServer``.

    Stateless between runs — ``SnapshotServer.run_multi`` constructs one
    per call when ``plan.scheduler == "continuous"``.
    """

    def __init__(self, server):
        self.srv = server

    # ---------------------------------------------------------- admission ----

    def _admit(self, qs, backlog, eof, active, sup: TenantSupervisor) -> None:
        """Drain every active producer queue non-blocking into the
        per-tenant backlogs. EOF marks the tenant draining; a producer
        exception (validation, no-fit bucket, injected fault) quarantines
        the tenant per policy and discards its backlog. Items from
        already-quarantined tenants are never admitted."""
        for sid in sorted(active):
            if sid in eof:
                continue
            while True:
                try:
                    item = qs[sid].get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    eof.add(sid)
                    break
                if isinstance(item, BaseException):
                    eof.add(sid)
                    backlog[sid].clear()
                    sup.quarantine(sid, item,
                                   site=getattr(item, "site", None))
                    break
                backlog[sid].append(item)

    @staticmethod
    def _ready(backlog, eof, active, sup: TenantSupervisor) -> list:
        """Retire quarantined and fully served tenants from ``active``;
        returns the tenants with snapshots ready."""
        for sid in list(active):
            if not sup.ok(sid):
                backlog[sid].clear()
                active.discard(sid)
            elif sid in eof and not backlog[sid]:
                active.discard(sid)  # stream fully served
        return [sid for sid in active if backlog[sid]]

    # --------------------------------------------------------------- run ----

    def run(self, params, states: dict, streams: dict, *,
            express_streams=None, express_params=None) -> tuple:
        """Serve ``streams`` to completion; same contract and return shape
        as the round-based ``run_multi`` (and bit-identical outputs/final
        states per tenant).

        Express tenants (``express_streams``, static family — see
        ``run_multi``) join the SAME tick loop: their backlogs admit and
        drain like everyone else's, but they bypass the state-pool
        working-set cap (stateless tenants hold no pages) and each tick's
        ready express slots co-batch into one dedicated stateless launch.
        """
        srv = self.srv
        if not srv._use_stream_batched():
            raise ValueError("the continuous scheduler requires the v3 "
                             "stream engine (plan validation enforces this)")
        sids = sorted(streams)
        x_sids = sorted(express_streams or {})
        x_set = set(x_sids)
        srv._trace = RunTrace()
        span = srv._trace.span
        qs, stop, threads = srv._spawn_producers(streams)
        if x_sids:
            xqs, x_threads = srv._spawn_express_producers(
                express_streams, stop)
            qs = {**qs, **xqs}
            threads = threads + x_threads
        outs: dict = {sid: [] for sid in sids + x_sids}
        lat: list = []
        ctr = {"live": 0, "padded": 0, "promoted": 0, "launches": 0,
               "timeouts": 0, "degraded": 0, "ticks": 0, "prefill": 0}
        sup = TenantSupervisor(sids + x_sids, srv._policy, outputs=outs)
        pool = TenantStatePool(states, srv.state_pool_pages, sup,
                               residency=srv.plan.state_residency)
        backlog: dict = {sid: deque() for sid in sids + x_sids}
        eof: set = set()
        last_tick = {sid: 0 for sid in sids + x_sids}
        active = set(sids) | x_set
        tick_no = 0
        try:
            with srv._fault_window():
                while active:
                    with span("serve.admit"):
                        self._admit(qs, backlog, eof, active, sup)
                    ready = self._ready(backlog, eof, active, sup)
                    if not ready and active:
                        # one span for the whole stretch of idle polls
                        with span("serve.idle"):
                            while not ready and active:
                                time.sleep(_IDLE_SLEEP_S)
                                self._admit(qs, backlog, eof, active, sup)
                                ready = self._ready(backlog, eof, active,
                                                    sup)
                    if not ready:
                        continue
                    # fairness under pool pressure: least-recently-
                    # scheduled first. Only RECURRENT tenants compete for
                    # the state-pool working set — stateless express
                    # tenants hold no pages, so they bypass the cap and
                    # ride every tick they have slots ready.
                    ready.sort(key=lambda s: (last_tick[s], repr(s)))
                    x_ready = [s for s in ready if s in x_set]
                    ready = [s for s in ready if s not in x_set]
                    if pool.capacity is not None:
                        # EFFECTIVE capacity: hbm_paged plans hold
                        # HBM_PAGE_FACTOR× more resident tenants per
                        # nominal page (see state_pool.TenantStatePool)
                        ready = ready[:pool.capacity]
                    tick_no += 1
                    ctr["ticks"] += 1
                    x_group: list = []
                    for sid in x_ready:
                        chunk: list = []
                        while backlog[sid] and len(chunk) < srv.stream_chunk:
                            ps, _ = backlog[sid].popleft()
                            chunk.append(ps)
                        x_group.append((sid, chunk))
                        last_tick[sid] = tick_no
                    if x_group:
                        srv._run_express_group(express_params, x_group,
                                               outs, lat, ctr, sup)
                    chunks: dict = {}
                    for sid in ready:
                        prefill = (srv.prefill_chunk is not None
                                   and len(backlog[sid]) > srv.stream_chunk)
                        quota = (srv.prefill_chunk if prefill
                                 else srv.stream_chunk)
                        chunk: list = []
                        dims: list = []
                        while backlog[sid] and len(chunk) < quota:
                            ls, d = backlog[sid].popleft()
                            chunk.append(ls)
                            dims.append(d)
                        chunks[sid] = (chunk, dims)
                        if prefill:
                            ctr["prefill"] += 1
                        last_tick[sid] = tick_no
                    # page the tick's working set in BEFORE any checkpoint
                    # is taken; evicts LRU tenants outside the set
                    with span("serve.pool"):
                        pool.acquire(list(chunks))
                    groups: dict = {}
                    for sid, (chunk, dims) in sorted(chunks.items()):
                        bucket = srv._chunk_bucket(dims)
                        groups.setdefault(bucket, []).append(
                            (sid, chunk, bucket))
                    if (srv.promote_buckets is not None
                            and srv.buckets is not None):
                        before = {b: len(m) for b, m in groups.items()}
                        groups = promote_bucket_groups(
                            groups, srv.buckets, srv.promote_buckets,
                            cost=srv._promotion_cost(params))
                        ctr["promoted"] += sum(
                            len(m) - before.get(b, 0)
                            for b, m in groups.items())
                    for bucket in sorted(groups):
                        srv._run_group_supervised(params, states,
                                                  groups[bucket], outs,
                                                  lat, ctr, sup)
        finally:
            # every tenant's state returns device-resident, wherever its
            # pages lived mid-run; then deterministic producer shutdown
            with span("serve.pool"):
                pool.flush()
            srv._shutdown(stop, list(qs.values()), threads)
        return states, outs, srv._make_stats(lat, ctr, sup)
