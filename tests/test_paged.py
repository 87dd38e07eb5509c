"""HBM-paged state residency (state_residency="hbm_paged").

The paging contract pinned here:

  1. EXACTNESS — for every stateful family, solo and batched (including
     ragged ``lengths``), an hbm_paged launch at ring depth 2 and 4 is
     BIT-IDENTICAL to the VMEM-resident launch: outputs and drained
     final states. Paging moves the store, never the math (every paged
     fill reproduces the resident cache columns window-by-window).
  2. CAPACITY — a store over the VMEM scratch budget is rejected under
     residency="vmem" with a hint to page, and RUNS under hbm_paged
     (matching the resident outputs computed under a roomier budget):
     the "larger than the old VMEM cap" unlock of the paging PR.
  3. ACCOUNTING — the plan-time estimator ``stream_vmem_bytes`` equals
     ``launch_scratch_bytes`` of the actually-assembled launch, for
     every family in resident, D-blocked, and paged (depth 2/4) layouts.
  4. NO FULL STORE — under paging, no family allocates a full
     ``(n_global, d_pad)`` (or ``(d_pad, d_pad)`` weights) plane in VMEM
     scratch: only ``td``-wide staging/ring windows transit VMEM, and
     the HBM store is aliased in-place (input_output_aliases).
  5. Static families have no state to page: kernel- and model-level
     rejection with the pinned message (plan-level lives in test_api.py).
"""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import harness
from repro import api
from repro.kernels import ops, stream_fused

STATEFUL = ("gcrn", "stacked", "evolve", "tgn")


def _assert_bitwise(got, want):
    ga, wa = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(ga) == len(wa)
    for g, w in zip(ga, wa):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert jnp.array_equal(g, w), "paged output diverged from resident"


@contextlib.contextmanager
def _capture_launch(family):
    """Spy on the family's registry build to capture the assembled
    ``_Launch`` (and the build's padded args/kwargs) at trace time."""
    spec = stream_fused.REGISTRY[family]
    box = {}

    def spy(*a, **kw):
        launch = spec.build(*a, **kw)
        box["launch"], box["args"], box["kw"] = launch, a, kw
        return launch

    stream_fused.REGISTRY[family] = dataclasses.replace(spec, build=spy)
    stream_fused.stream_call.clear_cache()
    try:
        yield box
    finally:
        stream_fused.REGISTRY[family] = spec
        stream_fused.stream_call.clear_cache()


def _dims(family, box):
    """Recover the estimator's inputs from the captured (padded) build
    args — same shape arithmetic as the builds themselves."""
    a, kw = box["args"], box["kw"]
    td = kw["td"]
    if family == "gcrn":
        n, din, h0 = a[0].shape[2], a[2].shape[3], a[5]
        G, h = h0.shape[1], h0.shape[2]
        return dict(g_rows=G, n_pad=n, din=din,
                    d_pad=stream_fused._round_up(h, td or h))
    if family == "stacked":
        n, h0, w_gcn = a[0].shape[2], a[5], a[6]
        G, h = h0.shape[1], h0.shape[2]
        return dict(g_rows=G, n_pad=n, dmid=w_gcn.shape[1],
                    d_pad=stream_fused._round_up(h, td or h))
    if family == "evolve":
        n, w0 = a[0].shape[2], a[5]
        return dict(n_pad=n, n_layers=w0.shape[1], d_pad=w0.shape[2])
    if family == "tgn":
        n, mem0 = a[0].shape[2], a[6]
        G, h = mem0.shape[1], mem0.shape[2]
        return dict(g_rows=G, n_pad=n,
                    d_pad=stream_fused._round_up(h, td or h))
    if family == "static_gcn":
        n, w = a[0].shape[2], a[4]
        return dict(n_pad=n, n_layers=w.shape[0], d_pad=w.shape[1])
    raise KeyError(family)


# ------------------------------------------------------- exactness ----

@pytest.mark.parametrize("family", STATEFUL)
@pytest.mark.parametrize("depth", [2, 4])
def test_paged_solo_bitwise(family, depth):
    args, _, _ = harness.stream_kernel_case(family, seed=3, T=3)
    want = ops.stream_steps(family, *args, tn=32, td=8)
    got = ops.stream_steps(family, *args, tn=32, td=8,
                           state_residency="hbm_paged", buffer_depth=depth)
    _assert_bitwise(got, want)


@pytest.mark.parametrize("family", STATEFUL)
@pytest.mark.parametrize("depth", [2, 4])
def test_paged_batched_ragged_bitwise(family, depth):
    args, _, _ = harness.stream_kernel_case(family, seed=11, T=4, B=3)
    for lengths in (None, (4, 2, 0)):
        want = ops.stream_steps_batched(family, *args, tn=32, td=8,
                                        lengths=lengths)
        got = ops.stream_steps_batched(family, *args, tn=32, td=8,
                                       lengths=lengths,
                                       state_residency="hbm_paged",
                                       buffer_depth=depth)
        _assert_bitwise(got, want)


def test_paged_through_plan_api():
    """plan(state_residency=, buffer_depth=) reaches the kernel through
    run_arrays — solo and batched-ragged — bit-identically."""
    args, _, _ = harness.stream_kernel_case("gcrn", seed=5, T=3)
    base = api.run_arrays(api.plan(family="gcrn", tn=32, td=8), *args)
    paged = api.run_arrays(
        api.plan(family="gcrn", tn=32, td=8,
                 state_residency="hbm_paged", buffer_depth=4), *args)
    _assert_bitwise(paged, base)

    argsB, _, _ = harness.stream_kernel_case("tgn", seed=6, T=4, B=3)
    pb = dict(family="tgn", tn=32, td=8, batch=3, lengths=(4, 2, 1))
    baseB = api.run_arrays(api.plan(**pb), *argsB)
    pagedB = api.run_arrays(
        api.plan(**pb, state_residency="hbm_paged", buffer_depth=2), *argsB)
    _assert_bitwise(pagedB, baseB)


# -------------------------------------------------------- capacity ----

def test_oversized_store_runs_only_paged(monkeypatch):
    """A state store over the VMEM budget must refuse to launch resident
    (with a hint to page) and run paged — matching the resident outputs
    computed under the roomy budget."""
    args, _, _ = harness.stream_kernel_case("gcrn", seed=9, T=3)
    want = ops.stream_steps("gcrn", *args, tn=32, td=8)
    with _capture_launch("gcrn") as box:
        ops.stream_steps("gcrn", *args, tn=32, td=8)
        resident_bytes = stream_fused.launch_scratch_bytes(box["launch"])
    with _capture_launch("gcrn") as box:
        ops.stream_steps("gcrn", *args, tn=32, td=8,
                         state_residency="hbm_paged", buffer_depth=2)
        paged_bytes = stream_fused.launch_scratch_bytes(box["launch"])
    assert paged_bytes < resident_bytes  # paging must actually shrink VMEM
    budget = (paged_bytes + resident_bytes) // 2
    monkeypatch.setattr(stream_fused, "VMEM_BUDGET_BYTES", budget)
    stream_fused.stream_call.clear_cache()
    try:
        with pytest.raises(ValueError, match="byte budget.*hbm_paged"):
            ops.stream_steps("gcrn", *args, tn=32, td=8)
        got = ops.stream_steps("gcrn", *args, tn=32, td=8,
                               state_residency="hbm_paged", buffer_depth=2)
        _assert_bitwise(got, want)
    finally:
        monkeypatch.undo()
        stream_fused.stream_call.clear_cache()


# ------------------------------------------------------ accounting ----

@pytest.mark.parametrize("family", STATEFUL + ("static_gcn",))
@pytest.mark.parametrize("residency,td,depth", [
    ("vmem", None, 2),       # fully resident
    ("vmem", 8, 2),          # D-blocked resident
    ("hbm_paged", 8, 2),     # double-buffered paging
    ("hbm_paged", 8, 4),     # quad-buffered paging
])
def test_scratch_byte_accounting(family, residency, td, depth):
    """Plan-time VMEM estimate == actual assembled pltpu.VMEM scratch."""
    if family == "static_gcn":
        if residency == "hbm_paged":
            pytest.skip("static_gcn cannot page (pinned below)")
        T = 1
    else:
        T = 3
    args, _, _ = harness.stream_kernel_case(family, seed=2, T=T)
    with _capture_launch(family) as box:
        kw = ({} if residency == "vmem"
              else dict(state_residency=residency, buffer_depth=depth))
        ops.stream_steps(family, *args, tn=32, td=td, **kw)
        actual = stream_fused.launch_scratch_bytes(box["launch"])
        est = stream_fused.stream_vmem_bytes(
            family, td=td, tn=box["kw"]["tn"], residency=residency,
            depth=depth, **_dims(family, box))
    assert actual == est, (
        f"{family}/{residency}/td={td}/depth={depth}: "
        f"assembled {actual} VMEM bytes, estimator says {est}")


@pytest.mark.parametrize("family", STATEFUL)
def test_no_full_store_in_vmem_when_paged(family):
    """Under paging no family may allocate a full-width state plane in
    VMEM scratch — only (rows, td) staging/ring windows — and the HBM
    store must be aliased in-place (zero-copy across the launch)."""
    args, _, _ = harness.stream_kernel_case(family, seed=4, T=3)
    with _capture_launch(family) as box:
        ops.stream_steps(family, *args, tn=32, td=8,
                         state_residency="hbm_paged", buffer_depth=2)
        launch = box["launch"]
        dims = _dims(family, box)
    d_pad = dims["d_pad"]
    assert d_pad > 8, "case must be D-blocked for the assertion to bite"
    full_rows = dims.get("g_rows", d_pad)  # weights plane is (d_pad, d_pad)
    for s in launch.scratch:
        if getattr(s, "memory_space", None) != stream_fused.pltpu.VMEM:
            continue
        assert s.shape[-2:] != (full_rows, d_pad), (
            f"{family}: full ({full_rows}, {d_pad}) state plane in VMEM "
            f"scratch under hbm_paged: {s.shape}")
    assert launch.aliases, (
        f"{family}: paged store must alias input->output (in-place HBM)")
    assert launch.meta.paged and launch.meta.depth == 2


# ------------------------------------------------- benchmark ledger ----

def test_write_stream_bench_dedupes_by_plan_signature(tmp_path):
    """Re-running a planned benchmark config replaces its ledger row
    instead of accumulating a sibling duplicate, even when the row name
    embeds run-varying counters (T8 vs T16); rows whose plans genuinely
    differ (e.g. buffer_depth) stay distinct, and un-planned rows keep
    keying by exact name."""
    import json

    from benchmarks.common import write_stream_bench

    path = tmp_path / "bench.json"
    plan_d2 = api.plan(family="gcrn", td=8, state_residency="hbm_paged",
                       buffer_depth=2).as_dict()
    plan_d4 = api.plan(family="gcrn", td=8, state_residency="hbm_paged",
                       buffer_depth=4).as_dict()
    write_stream_bench([("kernel/gcrn_paged_d2_T8", 10.0, "w=1"),
                        ("kernel/gcrn_paged_d4_T8", 11.0, "w=1")],
                       {"kernel/gcrn_paged_d2_T8": plan_d2,
                        "kernel/gcrn_paged_d4_T8": plan_d4}, path=path)
    # same configs re-run at a different sweep length: rows REPLACED
    write_stream_bench([("kernel/gcrn_paged_d2_T16", 9.0, "w=2")],
                       {"kernel/gcrn_paged_d2_T16": plan_d2}, path=path)
    # un-planned rows: keyed by exact name, overwrite on re-run
    write_stream_bench([("kernel/xla_ref", 5.0, "")], path=path)
    write_stream_bench([("kernel/xla_ref", 6.0, "")], path=path)
    rows = {r["name"]: r for r in json.loads(path.read_text())["rows"]}
    assert set(rows) == {"kernel/gcrn_paged_d2_T16",
                         "kernel/gcrn_paged_d4_T8", "kernel/xla_ref"}
    assert rows["kernel/gcrn_paged_d2_T16"]["us_per_call"] == 9.0
    assert rows["kernel/gcrn_paged_d2_T16"]["plan"]["buffer_depth"] == 2
    assert rows["kernel/xla_ref"]["us_per_call"] == 6.0


# ---------------------------------------------------- static family ----

def test_static_gcn_rejects_paging():
    args, _, _ = harness.stream_kernel_case("static_gcn", seed=1)
    with pytest.raises(ValueError, match="no recurrent store to page"):
        ops.stream_steps("static_gcn", *args, tn=32, td=8,
                         state_residency="hbm_paged", buffer_depth=2)


def test_static_gcn_model_rejects_paging():
    from repro.core.gcn import StaticGCN
    with pytest.raises(ValueError, match="no recurrent store to page"):
        StaticGCN._check_residency("hbm_paged", None)
    with pytest.raises(ValueError, match="no recurrent store to page"):
        StaticGCN._check_residency("vmem", 2)
    StaticGCN._check_residency("vmem", None)  # default is fine
