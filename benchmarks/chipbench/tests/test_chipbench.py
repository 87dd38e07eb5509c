"""CPU tests of the chip benchmark's harness (no chip needed).

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chipbench/tests

The end-to-end tests drive ``driver.run_cell`` at a tiny size (Pallas in
interpret mode), skipping only the look for a chip: the program serves,
the reference checks, and each planted fault in the timed path must turn
``correct`` false.
"""
from __future__ import annotations

import copy
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

from chipbench import catalog, driver, refcore, tracing, traffic  # noqa: E402

PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


# ------------------------------------------------------------ discovery ----

def test_every_name_resolves_to_its_files():
    bench = catalog.benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = catalog.config(c["name"])
        assert (ROOT / c["file"]).resolve() == (
            catalog.HERE / "configs" / f"{c['name']}.json").resolve()
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"] == []
        ref, work = catalog.family(cfg["family"])
        assert callable(ref.step) and callable(work.snapshot_flops)
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert catalog.traffic(w["traffic"])["mode"] in driver.RUNNERS
        assert catalog.cell(w["name"]) == w
        for trace in (False, True):
            assert catalog.metrics_for(w["name"], trace, bench)
    for spec in bench["end_to_end"] + bench["per_layer"]:
        assert callable(catalog.metric_reader(spec["name"]))
    assert catalog.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        catalog.peaks("cpu")


def test_benchmark_json_keeps_the_contract_shape():
    bench = catalog.benchmark()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and 0 < len(w["why"]) <= 200
        assert name.match(w["name"]) and name.match(w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for w in bench["workloads"]:
        reported = catalog.metrics_for(w["name"], False, bench)
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2


# -------------------------------------------------------------- traffic ----

DS = {"name": "tiny", "avg_nodes": 12, "avg_edges": 20, "max_nodes": 28,
      "max_edges": 48, "snapshots": 24, "global_nodes": 90}


def test_graph_stream_is_deterministic_per_seed_and_seeds_differ():
    a = traffic.graph_stream(DS, 2**31 + 5, 8, 4, (32, 128, 24))
    b = traffic.graph_stream(DS, 2**31 + 5, 8, 4, (32, 128, 24))
    c = traffic.graph_stream(DS, 2**31 + 6, 8, 4, (32, 128, 24))
    for (s1, d1, e1), (s2, d2, e2) in zip(a[0], b[0]):
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(e1, e2)
    np.testing.assert_array_equal(a[1], b[1])
    assert not all(s1.shape == s2.shape and (s1 == s2).all()
                   for (s1, _, _), (s2, _, _) in zip(a[0], c[0]))
    assert all(traffic.fits(s, 32, 128, 24) for s in a[0])


def test_open_loop_keeps_the_amount_of_work_across_seeds():
    one = traffic.open_loop(traffic.rng_for(1, 3), 137, 32, 200.0, 30.0, 1.0)
    two = traffic.open_loop(traffic.rng_for(2, 3), 137, 32, 200.0, 30.0, 1.0)
    counts = sorted(len(d) for _, d in one)
    assert counts == sorted(len(d) for _, d in two)
    assert abs(sum(counts) - 6000) <= 32
    assert [len(d) for _, d in one] != [len(d) for _, d in two]
    for _, due in one:
        assert np.all(np.diff(due) >= 0) and np.all((due >= 0) & (due < 30))
    assert traffic.walk(10, 8, 4) == [8, 9, 0, 1]


def test_sojourn_runs_from_due_time_to_commit():
    due = np.array([0.0, 0.010, 0.020])
    commits = [5.0, 12.5]  # ms since the window's origin; the third never
    np.testing.assert_allclose(traffic.sojourns_ms(due, commits), [5.0, 2.5])


# ---------------------------------------------------------------- work ----

def test_work_counts_match_a_hand_count():
    _, gcrn = catalog.family("gcrn")
    _, evolve = catalog.family("evolve")
    m = {"in_dim": 2, "hidden": 3, "edge_dim": 1, "out_dim": 2,
         "n_gnn_layers": 2}
    # n=2 nodes, e=1 edge -> a = 2*1 + 2 = 4 aggregated edges
    hand = 2 * (4 * 1 + 2 * 1 * 2 + 4 * 2 + 4 * 3 + 2 * 5 * 12 + 2 * 3 * 2)
    assert gcrn.snapshot_flops(m, 2, 1) == hand
    # layers (2 -> 3), (3 -> 2)
    l1 = 4 * 1 + 2 * 1 * 2 + 4 * 2 + 2 * 2 * 3 + 2 * 3 * 2 * 3 * 2
    l2 = 4 * 1 + 2 * 1 * 3 + 4 * 3 + 2 * 3 * 2 + 2 * 2 * 3 * 3 * 3
    assert evolve.snapshot_flops(m, 2, 1) == 2 * (l1 + l2)
    assert gcrn.state_bytes(m, 5) == 4 * 2 * 2 * 5 * 3
    assert evolve.state_bytes(m, 5) == 4 * 2 * (2 * 3 + 3 * 2)
    assert gcrn.weight_bytes(m) == 4 * ((2 + 3 + 1) * 12 + 4 * 2 + 1 * 2)


# -------------------------------------------------------------- traces ----

def _synthetic_trace() -> list:
    meta = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "thread_name", "pid": 9, "tid": 1,
         "args": {"name": "python"}},
    ]
    dev = [  # µs: busy [10, 30] u [25, 40] u [60, 70] -> 40 µs
        {"ph": "X", "pid": 1, "tid": 2, "ts": 10, "dur": 20,
         "name": "fusion.1"},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 25, "dur": 15,
         "name": "custom-call", "args": {"long_name": "stream_engine_gcrn"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 60, "dur": 10,
         "name": "stream_engine_gcrn"},
    ]
    host = [
        {"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 100,
         "name": tracing.SLICE_NAME},
        {"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 100,
         "name": "$engine.py:900 run_multi"},
        {"ph": "X", "pid": 9, "tid": 1, "ts": 0, "dur": 10,
         "name": "$padding.py:80 pad_snapshot"},
        {"ph": "X", "pid": 9, "tid": 1, "ts": 40, "dur": 20,
         "name": "$engine.py:412 _commit_group"},
        {"ph": "X", "pid": 9, "tid": 1, "ts": 45, "dur": 10,
         "name": "$numpy asarray"},
    ]
    return meta + dev + host


def test_trace_reduction_splits_kernel_glue_and_labels_gaps(tmp_path):
    events = _synthetic_trace()
    out = tracing.reduce(events, frozenset({"engine.py", "padding.py"}))
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(40e-6)
    assert out["kernel_s"] == pytest.approx(25e-6)
    assert out["glue_s"] == pytest.approx(20e-6)
    assert tracing.idle_pct(out) == pytest.approx(60.0)
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({
        "padding.py:pad_snapshot": 10e-6,
        "engine.py:_commit_group > numpy:asarray": 20e-6,
        "engine.py:run_multi": 30e-6})
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion.1": 20e-6, "custom-call": 15e-6,
         "stream_engine_gcrn": 10e-6})


def test_a_recorded_profile_loads_with_its_slice_and_frames(tmp_path):
    import jax
    import jax.numpy as jnp

    def host_work():
        return float(jnp.ones((64, 64)).sum())

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tracing.SLICE_NAME):
            host_work()
            time.sleep(0.02)
    events = tracing.load(tmp_path)
    out = tracing.reduce(events)
    assert 0.02 <= out["window_s"] < 5.0
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert any(n.endswith(" host_work") for n in names)
    assert out["chips"] == 0 and out["busy_s"] is None  # a CPU has none


def test_union_merges_overlaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]


# ------------------------------------------------------------ reference ----

def test_control_rounds_to_a_bfloat16_pair():
    a = np.random.default_rng(0).normal(size=(16, 32)).astype(np.float32)
    b = np.random.default_rng(1).normal(size=(32, 8)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    hi = np.asarray(refcore.dot_fn("highest")(a, b))
    lo = np.asarray(refcore.dot_fn("high")(a, b))
    assert np.abs(hi - exact).max() < 1e-5
    assert 1e-6 < np.abs(lo - exact).max() < 1e-3


# ------------------------------------------------------------ end to end ----

def tiny(family: str, mode: str) -> tuple:
    name = "gcrn-m2-bcalpha" if family == "gcrn" else "evolvegcn-o-bcalpha"
    cfg = copy.deepcopy(catalog.config(name))
    cfg["model"].update(in_dim=16, hidden=32, out_dim=8, edge_dim=4)
    cfg["dataset"] = dict(DS)
    cfg["plan"].update(n_pad=32, e_pad=128, k_max=24, stream_chunk=4)
    if mode == "replay":
        mix = dict(catalog.traffic("bcalpha-replay16"), tenants=4, history=8,
                   check_streams=3)
    else:
        mix = dict(catalog.traffic("bcalpha-online32-gcrn"), tenants=6,
                   rate_per_s=20.0)
    return cfg, mix


@pytest.fixture(autouse=True)
def short_harness(monkeypatch):
    """The harness's fixed behaviour, shrunk to the tiny cells."""
    monkeypatch.setattr(driver.Replay, "RETAIN_PER_PASS", 4)
    monkeypatch.setattr(driver.OpenLoop, "WARM_ROUNDS_MAX", 1)
    monkeypatch.setattr(driver.OpenLoop, "WARM_SECONDS", 0.5)
    monkeypatch.setattr(driver.OpenLoop, "WARM_SEGMENTS_MAX", 2)
    monkeypatch.setattr(driver.OpenLoop, "TRACE_SECONDS", 0.5)


def run_tiny(cfg, mix, seconds=1.0, control=False, tmp=None):
    rec, check, device = driver.run_cell(
        cfg, mix, 2**31 + 17, seconds, False, time.perf_counter(),
        tmp, peaks=PEAKS, control=control)
    return rec, check, driver.verdict(rec, check, cfg["limits"])


@pytest.mark.parametrize("family,mode", [("gcrn", "replay"),
                                         ("evolve", "open_loop")])
def test_sound_run_is_correct_and_the_control_is_not(family, mode, tmp_path):
    cfg, mix = tiny(family, mode)
    rec, check, ok = run_tiny(cfg, mix, control=True, tmp=tmp_path)
    assert ok, check
    assert rec.attempted > 0 and rec.failed == 0 and rec.live > 0
    if mode == "replay":  # one launch shape, warmed by one pass
        assert rec.lowerings_in_window == 0
    assert check["control_out_gap"] > 10 * max(check["out_gap"], 1e-8)


@pytest.mark.parametrize("name", ["gcrn-m2-bcalpha", "evolvegcn-o-bcalpha"])
def test_the_control_at_the_cells_size_is_not_correct(name):
    """At the configuration's own widths, graph stream and replay length,
    the harness's own comparison passes the reference in the program's
    place and fails the control (the reference at ``high`` precision)
    against the configuration's limits."""
    import jax

    cfg = catalog.config(name)
    ref, work = catalog.family(cfg["family"])
    m, p = cfg["model"], cfg["plan"]
    bucket = (p["n_pad"], p["e_pad"], p["k_max"])
    snaps, feat, n_global = traffic.graph_stream(
        cfg["dataset"], 2**31 + 29, m["in_dim"], m["edge_dim"], bucket)
    stream = driver.Stream(snaps, work, m)
    params = jax.jit(lambda k: ref.make_params(k, m))(jax.random.key(29))
    idx = list(range(catalog.traffic("bcalpha-replay16")["history"]))
    graphs = [refcore.prepare(*snaps[k], *bucket[:2], n_global) for k in idx]
    outs, state = refcore.run_stream(ref, params, m, n_global, graphs, feat,
                                     "highest")
    sample = [(idx, [(o, 0.0) for o in outs], state)]
    check = driver.compare(ref, params, m, n_global, stream, feat, bucket,
                           sample, control=True)
    rec = driver.Record(mode="replay", peaks=PEAKS, attempted=len(idx))
    assert driver.verdict(rec, check, cfg["limits"]), check
    assert not driver.verdict(rec, driver.control_numbers(check),
                              cfg["limits"]), check


def _state_unchanged(orig):
    def step(self, params, state, snaps, **kw):
        _, out = orig(self, params, state, snaps, **kw)
        return state, out
    return step


def _half_batch_left_out(orig):
    def step(self, params, state, snaps, **kw):
        import jax

        new, out = orig(self, params, state, snaps, **kw)
        half = out.shape[0] // 2
        new = jax.tree.map(lambda n, o: n.at[half:].set(o[half:]), new,
                           state)
        return new, out.at[half:].set(0.0)
    return step


def _answer_altered(orig):
    def step(self, params, state, snaps, **kw):
        new, out = orig(self, params, state, snaps, **kw)
        return new, out.at[:, 0, 0, 0].add(1e-3)
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out,
                                   _answer_altered])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch, tmp_path):
    """One chip, no collective: the faults a cell can have are a step that
    returns its state unchanged, half of each launch left out, and an
    answer altered where it is produced."""
    from repro.core.gcrn import GCRN

    monkeypatch.setattr(GCRN, "step_stream_batched",
                        fault(GCRN.step_stream_batched))
    cfg, mix = tiny("gcrn", "replay")
    rec, check, ok = run_tiny(cfg, mix, tmp=tmp_path)
    assert rec.live > 0
    assert not ok, check


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(catalog.HERE / "run.py"), "--workload",
         "gcrn-bcalpha-replay", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
