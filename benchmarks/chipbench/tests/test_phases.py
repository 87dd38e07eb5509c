"""CPU tests of ``chipbench/phases.py``: the readers of the program's
serve spans, stamps and named scopes (no chip needed).

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chipbench/tests
"""
from __future__ import annotations

import copy
import sys
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

from chipbench import catalog, driver, phases, tracing, traffic  # noqa: E402


def _meta() -> list:
    return [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "thread_name", "pid": 9, "tid": 1,
         "args": {"name": "python"}},
        {"ph": "M", "name": "thread_name", "pid": 9, "tid": 3,
         "args": {"name": "python"}},
    ]


def _x(pid, tid, ts, dur, name, **args) -> dict:
    ev = {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
          "name": name}
    if args:
        ev["args"] = args
    return ev


def _serve_trace() -> list:
    """µs: a 100 µs slice. Device busy [20, 30] u [35, 45] u [70, 80].
    The loop's spans: wait_producers [0, 15]; launch [15, 60] with
    stage [15, 25], dispatch [25, 32], device_wait [32, 50],
    unstage [50, 55]; nothing [60, 65]; wait_producers [65, 100]. A
    producer thread's prep span covers the whole slice and must not
    count."""
    dev = [
        _x(1, 2, 20, 10, "fusion.7",
           tf_op="jit(step)/jit(main)/edge_aggregate/gather"),
        _x(1, 2, 35, 10, "stream_engine_gcrn",
           tf_op="jit(step)/edge_aggregate/pallas_call"),
        _x(1, 2, 70, 6, "copy.3", tf_op="jit(step)/edge_aggregate"),
        _x(1, 2, 76, 4, "fusion.9", tf_op="jit(step)/edge_aggregate_x/mul"),
    ]
    loop = [
        _x(9, 1, 0, 100, tracing.SLICE_NAME),
        _x(9, 1, 0, 15, "serve.wait_producers"),
        _x(9, 1, 15, 45, "serve.launch"),
        _x(9, 1, 15, 10, "serve.stage"),
        _x(9, 1, 25, 7, "serve.dispatch"),
        _x(9, 1, 32, 18, "serve.device_wait"),
        _x(9, 1, 50, 5, "serve.unstage"),
        _x(9, 1, 65, 35, "serve.wait_producers"),
        _x(9, 1, 40, 5, "$engine.py:600 _stage_group"),
    ]
    prep = [_x(9, 3, 0, 100, "serve.prep")]
    return _meta() + dev + loop + prep


def test_innermost_segments_name_each_piece_by_the_deepest_span():
    segs = phases.innermost_segments([(0, 10, "a"), (2, 4, "b"),
                                      (4, 6, "c"), (12, 14, "d")])
    assert segs == [(0, 2, "a"), (2, 4, "b"), (4, 6, "c"), (6, 10, "a"),
                    (12, 14, "d")]


def test_span_gaps_split_idle_time_by_the_loops_innermost_span():
    gaps = dict(phases.span_gaps(_serve_trace()))
    # idle: [0, 20], [30, 35], [45, 70], [80, 100] -> 70 µs
    assert gaps == pytest.approx({
        "serve.wait_producers": 15e-6 + 5e-6 + 20e-6,
        "serve.stage": 5e-6,
        "serve.dispatch": 2e-6,
        "serve.device_wait": 3e-6 + 5e-6,
        "serve.unstage": 5e-6,
        "serve.launch": 5e-6,
        phases.NO_SPAN: 5e-6})
    assert sum(gaps.values()) == pytest.approx(70e-6)
    # the same idle time the kept reduction finds
    red = tracing.reduce(_serve_trace())
    assert red["window_s"] - red["busy_s"] == pytest.approx(70e-6)


def test_scope_s_sums_the_glue_ops_that_carry_the_scope():
    ev = _serve_trace()
    ops = phases.scope_ops(ev, "edge_aggregate")
    # the kernel op is never glue; "edge_aggregate_x" is another scope
    assert ops == pytest.approx({"fusion.7": 10e-6, "copy.3": 6e-6})
    assert phases.scope_s(ev, "edge_aggregate") == pytest.approx(16e-6)
    assert phases.scope_s(ev, "head") == 0.0
    assert phases.scope_s(_meta(), "edge_aggregate") == 0.0


def test_scope_from_the_instructions_op_name_metadata():
    """A TPU op event names its HLO instruction, not its metadata: the
    scope is found through the profile's HLO protos (``hlo_op_names``)."""
    ev = _meta() + [
        _x(1, 2, 10, 8, "%fusion = f32[64]{0} fusion(f32[8,64]{1,0} %p), "
           "kind=kLoop, calls=%fused", hlo_module="jit_step"),
        _x(1, 2, 20, 4, "%fusion = f32[64]{0} fusion(f32[64]{0} %q)",
           hlo_module="jit_other"),
        _x(1, 2, 30, 2, "%copy-start.3 = (s32[8]{0}) copy-start(%e)"),
        _x(9, 1, 0, 100, tracing.SLICE_NAME)]
    op_names = {"jit_step": {"fusion": "jit(step)/edge_aggregate/mul"},
                "jit_other": {"fusion": "jit(other)/mul",
                              "copy-start.3": "jit(x)/edge_aggregate"}}
    ops = phases.scope_ops(ev, "edge_aggregate", op_names)
    # the module on the event decides; without one, any module's entry
    assert list(ops) == [ev[5]["name"], ev[7]["name"]]
    assert phases.scope_s(ev, "edge_aggregate", op_names) == pytest.approx(
        10e-6)
    assert phases.scope_s(ev, "edge_aggregate") == 0.0


def test_a_recorded_profile_keeps_the_scope_in_its_hlo(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("edge_aggregate"):
            y = x @ x
        return (2.0 * y).sum()

    x = jnp.ones((16, 16))
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(x).block_until_ready()
    op_names = phases.hlo_op_names(tmp_path)
    table = op_names["jit_step"]
    assert any(v.endswith("/edge_aggregate/dot_general")
               for v in table.values())
    assert not any("edge_aggregate" in v for k, v in table.items()
                   if "mul" in k)


def _stats(**kw):
    base = dict(phase_ms={}, phase_n={}, total_ms=0.0, live_snapshots=0,
                preprocess_ms=[], preprocess_cpu_ms=[], commit_ms={},
                arrive_ms={}, ready_ms={}, launch_start_ms={})
    return types.SimpleNamespace(**{**base, **kw})


def test_phase_readers_on_hand_built_stats():
    one = _stats(phase_ms={"serve.wait_producers": 10.0,
                           "serve.launch": 70.0, "serve.checkpoint": 1.0,
                           "serve.stage": 20.0, "serve.stack_batch": 4.0,
                           "serve.dispatch": 6.0, "serve.device_wait": 30.0,
                           "serve.unstage": 3.0, "serve.commit": 2.0,
                           "serve.prep": 99.0},
                 total_ms=100.0, live_snapshots=10,
                 preprocess_ms=[3.0, 5.0], preprocess_cpu_ms=[1.0, 2.0])
    tot = phases.totals([one, one])
    assert tot["live"] == 20 and tot["total_ms"] == 200.0
    got = phases.per_snapshot(tot)
    assert got["ms_per_snap"] == pytest.approx({
        "producer_wait": 1.0, "stage": 2.4, "dispatch": 0.6,
        "device_wait": 3.0, "unstage": 0.6})
    assert got["covered_pct"] == pytest.approx(76.0)
    assert got["top_covered_pct"] == pytest.approx(80.0)
    assert got["rest_ms_per_snap"] == pytest.approx({
        "launch_outside_phases": 0.4, "spawn": 0.0, "shutdown": 0.0,
        "outside_loop_spans": 2.0})
    assert got["prep_cpu_ms_per_snap"] == pytest.approx(1.5)
    assert got["prep_ms_per_snap"] == pytest.approx(4.0)
    assert phases.per_snapshot(phases.totals([])) == {}


def test_online_parts_telescope_to_the_sojourn():
    sched = [(0, np.array([0.010, 0.020, 0.030])), (5, np.array([0.005]))]
    st = _stats(commit_ms={"t00": [14.0, 26.0], "t01": [9.0]},
                arrive_ms={"t00": [10.5, 20.1, 30.0], "t01": [5.2]},
                ready_ms={"t00": [11.0, 21.0, 30.5], "t01": [6.0]},
                launch_start_ms={"t00": [12.0, 24.0], "t01": [7.5]})
    parts = phases.online_parts(sched, st)
    want = np.concatenate([traffic.sojourns_ms(d, st.commit_ms[f"t{i:02d}"])
                           for i, (_, d) in enumerate(sched)])
    np.testing.assert_allclose(parts["sojourn"], want)
    summed = np.sum([parts[k] for k in ("late", "arrive_to_ready",
                                        "ready_to_launch",
                                        "launch_to_commit")], axis=0)
    np.testing.assert_allclose(summed, want)
    np.testing.assert_allclose(parts["late"], [0.5, 0.1, 0.2])
    np.testing.assert_allclose(parts["launch_to_commit"], [2.0, 2.0, 1.5])


# ------------------------------------------------------------ end to end ----

DS = {"name": "tiny", "avg_nodes": 12, "avg_edges": 20, "max_nodes": 28,
      "max_edges": 48, "snapshots": 24, "global_nodes": 90}


def _tiny(mode: str) -> tuple:
    cfg = copy.deepcopy(catalog.config("gcrn-m2-bcalpha"))
    cfg["model"].update(in_dim=16, hidden=32, out_dim=8, edge_dim=4)
    cfg["dataset"] = dict(DS)
    cfg["plan"].update(n_pad=32, e_pad=128, k_max=24, stream_chunk=4)
    if mode == "replay":
        mix = dict(catalog.traffic("bcalpha-replay16"), tenants=4,
                   history=8)
    else:
        mix = dict(catalog.traffic("bcalpha-online32-gcrn"), tenants=6,
                   rate_per_s=20.0)
    return cfg, mix


@pytest.mark.parametrize("mode", ["replay", "open_loop"])
def test_measure_reads_the_programs_spans(mode, monkeypatch, tmp_path):
    monkeypatch.setattr(driver.OpenLoop, "WARM_ROUNDS_MAX", 1)
    monkeypatch.setattr(driver.OpenLoop, "WARM_SECONDS", 0.5)
    monkeypatch.setattr(driver.OpenLoop, "WARM_SEGMENTS_MAX", 1)
    monkeypatch.setattr(driver.OpenLoop, "TRACE_SECONDS", 0.5)
    cfg, mix = _tiny(mode)
    out = phases.measure(cfg, mix, 2**31 + 23, 1.0, tmp_path / "trace")
    assert out["live"] > 0
    parts = out["ms_per_snap"]
    assert parts["dispatch"] > 0 and parts["device_wait"] > 0
    assert out["phase_n"]["serve.launch"] == out["phase_n"]["serve.stage"]
    assert 0.0 < out["covered_pct"] <= 100.0
    assert out["prep_cpu_ms_per_snap"] > 0
    if mode == "replay":
        assert out["covered_pct"] > 50.0
    else:
        assert out["telescope_max_err_ms"] < 1e-6
        assert set(out["parts_p50_ms"]) == {
            "late", "arrive_to_ready", "ready_to_launch",
            "launch_to_commit", "sojourn"}
    # the slice's spans are in the profile; a CPU has no device line
    assert out["slice"]["live"] > 0 and out["slice"]["idle_by_span"] == []
    assert not (tmp_path / "trace").exists()
