"""Named spans and per-snapshot stamps of one serve run.

``RunTrace.span(name, **args)`` is the serve path's one span helper. It
opens a ``jax.profiler.TraceAnnotation`` of that name, so under an active
profiler the span lands in the profile on the host thread that ran it, on
the same clock as the device's op lines; without one the annotation costs
about a microsecond. It also adds the span's wall time to the run's
``phase_ms[name]`` and 1 to ``phase_n[name]``: those counters are always
on, and ``ServeStats`` returns them.

Span names (every one starts ``serve.``):

  serve.launch       one launch attempt of a batched group (parent of the
                     next six); its start is the snapshots' launch stamp
  serve.checkpoint   tenant-state checkpoint, and rollback on a failure
  serve.stage        chunk padding, time stacking, batch-row padding, the
                     tenant-state stack: everything before the timed launch
  serve.stack_batch  the B-axis stack of the (B, T) batch    } together the
  serve.dispatch     the jitted call, host-to-device copy    } launch wall
  serve.device_wait  blocking on the launch's result         } of a launch
  serve.unstage      output to host, per-tenant state slices
  serve.commit       the state and output commit
  serve.express      one express-lane launch attempt, commit included
  serve.wait_producers  a round's (or a snapshot's) pull from the producers
  serve.admit        the continuous scheduler's admission
  serve.idle         one stretch of the continuous scheduler's idle sleeps
  serve.pool         tenant-state pool acquire and flush
  serve.prep         one snapshot's host prep, on its producer thread
  serve.spawn        starting one producer thread
  serve.shutdown     stopping and joining the producer threads

Stamps are ms since the run's start, per tenant in stream order, on the
clock of ``commit_ms``: due -> arrive -> ready -> launch start -> commit
telescopes to a snapshot's sojourn.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation


class _Span:
    __slots__ = ("trace", "name", "note", "t0", "t1", "ms")

    def __init__(self, trace: "RunTrace", name: str, start, args: dict):
        self.trace, self.name, self.t0 = trace, name, start
        self.note = TraceAnnotation(name, **args)
        self.ms = 0.0

    @property
    def start_ms(self) -> float:
        """When the span opened, in ms since the run's start."""
        return (self.t0 - self.trace.t0) * 1e3

    def __enter__(self) -> "_Span":
        self.note.__enter__()
        if self.t0 is None:
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self.ms = (self.t1 - self.t0) * 1e3
        self.note.__exit__(*exc)
        self.trace.add(self.name, self.ms)


class RunTrace:
    """What one serve run measured of itself: span totals, host prep per
    snapshot, and each snapshot's stamps."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.phase_ms: dict = {}
        self.phase_n: dict = {}
        self.prep_ms: list = []      # wall of each prepared snapshot
        self.prep_cpu_ms: list = []  # its producer thread's CPU time
        self.arrive_ms: dict = {}
        self.ready_ms: dict = {}
        self.launch_start_ms: dict = {}
        self.commit_ms: dict = {}
        self._lock = threading.Lock()  # producers add spans concurrently

    def span(self, name: str, start: float | None = None, **args) -> _Span:
        """``with trace.span("serve.stage", launch=k, B=b, T=t):``; keyword
        arguments become the annotation's metadata. ``start`` (a
        ``perf_counter`` reading, such as the previous span's ``t1``)
        counts the span from there instead of from its entry, so that
        back-to-back spans add up to the wall they cover."""
        return _Span(self, name, start, args)

    def add(self, name: str, ms: float) -> None:
        with self._lock:
            self.phase_ms[name] = self.phase_ms.get(name, 0.0) + ms
            self.phase_n[name] = self.phase_n.get(name, 0) + 1

    def now_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3

    def stamps(self) -> dict:
        """Per-tenant stamp lists cut to the tenant's committed snapshots
        (a producer may have prepared more than a quarantined or stopped
        tenant served): ``{"arrive_ms", "ready_ms", "launch_start_ms"}``."""
        out = {}
        for key in ("arrive_ms", "ready_ms", "launch_start_ms"):
            src = getattr(self, key)
            out[key] = {sid: list(src.get(sid, ()))[:len(c)]
                        for sid, c in self.commit_ms.items()}
        return out
