"""Serve-path spans, counters and per-snapshot stamps (serve/spans.py).

What these tests pin, on both multi-tenant schedulers at a tiny size:

  * every launch phase is counted once per launch attempt, and the timed
    phases (``serve.stack_batch`` + ``serve.dispatch`` +
    ``serve.device_wait``) add up to the launch walls ``per_snapshot_ms``
    reports;
  * each tenant's stamps line up with its commits and are ordered
    arrive <= ready <= launch start <= commit;
  * a running profiler changes nothing that is served, and its profile
    holds the loop's spans on the loop's thread and ``serve.prep`` on the
    producer threads;
  * the glue ops carry their named scopes in the lowered step, and no
    span or scope name carries the stream-engine kernel's mark.
"""
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.configs.dgnn import DGNNConfig
from repro.graph.coo import COOSnapshot
from repro.graph.padding import stack_streams
from repro.serve import SnapshotServer

N_GLOBAL = 32
CHUNK = 2
LENS = {"a": 5, "b": 3, "c": 4}
CFG = DGNNConfig(name="spans-gcrn", dgnn_type="integrated", gnn="gcn",
                 rnn="lstm", dataflow="v3", in_dim=4, hidden=8, out_dim=4,
                 n_gnn_layers=1, edge_dim=2)
FEAT = np.asarray(np.random.default_rng(3).normal(size=(N_GLOBAL, 4)),
                  np.float32)
# the spans of one launch attempt of the batched path
LAUNCH_PHASES = ("serve.launch", "serve.checkpoint", "serve.stage",
                 "serve.stack_batch", "serve.dispatch", "serve.device_wait",
                 "serve.unstage", "serve.commit")
LOOP_PHASE = {"rounds": "serve.wait_producers", "continuous": "serve.admit"}
KERNEL_MARK = "stream_engine"  # what a profile reader takes for the kernel


def _snaps(ix, n):
    r = np.random.default_rng(101 + ix)
    out = []
    for t in range(n):
        e = int(r.integers(3, 7))
        out.append(COOSnapshot(
            src=r.integers(0, N_GLOBAL, size=e),
            dst=r.choice(N_GLOBAL, size=e, replace=False),
            edge_feat=np.asarray(r.normal(size=(e, 2)), np.float32),
            t_index=t))
    return out


def _streams():
    return {sid: _snaps(i, n) for i, (sid, n) in enumerate(sorted(
        LENS.items()))}


def _server(scheduler):
    plan = api.plan(CFG, level="v3", n_pad=16, e_pad=32, k_max=8,
                    stream_chunk=CHUNK, scheduler=scheduler)
    sess = api.BoosterSession(CFG, plan, n_global=N_GLOBAL, feat_table=FEAT)
    return SnapshotServer(session=sess)


def _serve(srv, streams):
    params, _ = srv.init(jax.random.PRNGKey(0))
    states = {sid: srv.model.init_state(params, mode=srv.mode)
              for sid in streams}
    return srv.run_multi(params, states, streams)


@pytest.fixture(scope="module", params=sorted(LOOP_PHASE))
def served(request):
    """One warm serve per scheduler: ``(scheduler, streams, stats)``."""
    srv = _server(request.param)
    streams = _streams()
    _serve(srv, streams)  # compiles every launch shape
    _, _, stats = _serve(srv, streams)
    return request.param, streams, stats


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The same rounds-loop serve without and then under
    ``jax.profiler.trace``: ``(plain, traced, profile directory)``, each
    a ``(states, outs, stats)``."""
    srv = _server("rounds")
    streams = _streams()
    _serve(srv, streams)
    plain = _serve(srv, streams)
    out_dir = tmp_path_factory.mktemp("serve-profile")
    with jax.profiler.trace(str(out_dir)):
        traced = _serve(srv, streams)
    return plain, traced, out_dir


def test_launch_phases_counted_once_per_attempt(served):
    scheduler, streams, stats = served
    assert stats.launches > 0 and not stats.retries
    for name in LAUNCH_PHASES:
        assert stats.phase_n[name] == stats.launches, name
    assert stats.phase_n[LOOP_PHASE[scheduler]] > 0
    n_snaps = sum(LENS.values())
    assert stats.phase_n["serve.prep"] == n_snaps
    assert len(stats.preprocess_ms) == len(stats.preprocess_cpu_ms) == n_snaps
    assert all(v >= 0.0 for v in stats.phase_ms.values())
    if scheduler == "continuous":
        assert stats.phase_n["serve.pool"] == stats.ticks + 1  # + flush


def test_launch_wall_is_stack_dispatch_wait(served):
    _, _, stats = served
    wall = float(np.sum(stats.per_snapshot_ms))
    parts = sum(stats.phase_ms[k] for k in ("serve.stack_batch",
                                            "serve.dispatch",
                                            "serve.device_wait"))
    assert wall > 0.0
    assert abs(parts - wall) <= 0.01 * wall
    # the per-snapshot prints read the same counters
    assert stats.device_wait_ms_per_snapshot == pytest.approx(
        stats.phase_ms["serve.device_wait"] / sum(LENS.values()))


def test_stamps_align_with_commits_in_order(served):
    _, streams, stats = served
    assert set(stats.commit_ms) == set(streams)
    for sid, commit in stats.commit_ms.items():
        assert len(commit) == len(streams[sid])
        arrive = stats.arrive_ms[sid]
        ready = stats.ready_ms[sid]
        launch = stats.launch_start_ms[sid]
        assert len(arrive) == len(ready) == len(launch) == len(commit)
        for a, r, s, c in zip(arrive, ready, launch, commit):
            assert 0.0 <= a <= r <= s <= c <= stats.total_ms
        # stream order: a tenant's stamps never run backwards
        for xs in (arrive, ready, launch, commit):
            assert list(xs) == sorted(xs)


def test_profiler_changes_nothing_served(profiled):
    (st_a, outs_a, _), (st_b, outs_b, stats_b), _ = profiled
    assert set(outs_a) == set(outs_b) == set(LENS)
    for sid in LENS:
        assert len(outs_a[sid]) == len(outs_b[sid]) == LENS[sid]
        for x, y in zip(outs_a[sid], outs_b[sid]):
            np.testing.assert_array_equal(x, y)
        for x, y in zip(jax.tree.leaves(st_a[sid]),
                        jax.tree.leaves(st_b[sid])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for name in LAUNCH_PHASES:
        assert stats_b.phase_n[name] == stats_b.launches


def test_profile_holds_spans_on_their_threads(profiled):
    from jax.profiler import ProfileData

    *_, out_dir = profiled
    files = sorted(Path(out_dir).rglob("*.xplane.pb"))
    assert len(files) == 1
    lines = []
    for plane in ProfileData.from_file(str(files[0])).planes:
        for line in plane.lines:
            names = [e.name for e in line.events
                     if e.name.startswith("serve.")]
            if names:
                lines.append(names)
    loop = [ns for ns in lines if "serve.launch" in ns]
    assert len(loop) == 1, "every launch span on one thread"
    loop_names = set(loop[0])
    assert set(LAUNCH_PHASES) | {"serve.wait_producers"} <= loop_names
    assert "serve.prep" not in loop_names
    prep = [ns for ns in lines if "serve.prep" in ns]
    # one producer thread per tenant, each preparing its whole stream
    assert sorted(ns.count("serve.prep") for ns in prep) == sorted(
        LENS.values())


def _lowered_hlo():
    """Compiled HLO text of the server's batched stream step on a B=2,
    T=2 batch, op metadata included."""
    srv = _server("rounds")
    params, state = srv.init(jax.random.PRNGKey(0))
    ps, _ = srv._prepare(_snaps(0, 1)[0])
    from repro.core import stack_time

    batch = stack_streams([stack_time([ps, ps])] * 2)
    states_B = jax.tree.map(lambda a: np.stack([a, a]), state)
    lowered = srv._stream_step_batched.lower(
        params, states_B, batch, np.asarray([2, 1], np.int32))
    return lowered.compile().as_text()


def test_glue_ops_carry_named_scopes():
    hlo = _lowered_hlo()
    scoped = [ln for ln in hlo.splitlines()
              if "/edge_aggregate/" in ln and "op_name=" in ln]
    assert scoped, "the edge_aggregate gather carries its scope"
    assert any("gather" in ln for ln in scoped)
    assert "/edge_project/" in hlo
    assert "/head/" in hlo
    # the kernel's mark never rides on a glue op's scope, so a profile
    # reader that picks the kernel out by it cannot take the glue for it
    for scope in ("edge_aggregate", "edge_project", "head"):
        for ln in hlo.splitlines():
            if f"/{scope}/" in ln:
                assert KERNEL_MARK not in ln.split("op_name=")[1], ln


def test_span_names_are_serve_names(served):
    _, _, stats = served
    assert stats.phase_ms and set(stats.phase_ms) == set(stats.phase_n)
    for name in stats.phase_ms:
        assert name.startswith("serve.") and KERNEL_MARK not in name


def test_single_stream_run_spans_and_stamps():
    """``run`` (one tenant) takes the same producer and launch spans."""
    srv = _server("rounds")
    params, state = srv.init(jax.random.PRNGKey(0))
    snaps = _snaps(0, 5)
    srv.run(params, state, snaps)
    _, outs, stats = srv.run(params, state, snaps)
    assert len(outs) == 5
    assert stats.phase_n["serve.prep"] == 5
    assert stats.phase_n["serve.wait_producers"] >= 5
    for name in LAUNCH_PHASES:
        assert stats.phase_n[name] == stats.launches
    (sid,) = stats.commit_ms
    assert len(stats.arrive_ms[sid]) == len(stats.launch_start_ms[sid]) == 5
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith("dgnn-serve")]


def test_span_counters_survive_concurrent_producers():
    """Producer threads add ``serve.prep`` spans while the loop adds its
    own: no update may be lost (more threads than cores, a switch
    interval short enough to preempt inside the update)."""
    import os
    import sys

    from repro.serve.spans import RunTrace

    trace = RunTrace()
    n_threads = 2 * (os.cpu_count() or 4)
    per_thread = 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with trace.span("serve.prep"):
                    pass
                trace.add("serve.launch", 1.0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    total = n_threads * per_thread
    assert trace.phase_n == {"serve.prep": total, "serve.launch": total}
    assert trace.phase_ms["serve.launch"] == float(total)
