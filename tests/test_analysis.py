"""repro.analysis — every rule fires on an injected violation, and the
shipped tree is clean.

Three kinds of injection, one per pass group:
  * contract rules: monkeypatch the engine's paged-DMA protocol (or
    doctor a captured _Launch) and re-run the recording-shim sweep;
  * lint rules: synthesized snippet files under tmp_path, fed through
    ``run_lint(root, files=[...])``;
  * drift rules: a fake registry family / a doctored docs copy against
    the real artifacts.

The clean-tree smoke at the end pins the acceptance criterion: zero
findings, zero suppressions, byte-stable JSON.
"""
import dataclasses
import textwrap

import jax.numpy as jnp
import pytest

import repro.analysis as analysis
from repro.analysis import cases, contracts, core, drift, lint
from repro.analysis.contracts import Point
from repro.kernels import stream_fused

pl = stream_fused.pl
pltpu = stream_fused.pltpu

PAGED_STACKED = Point("stacked", "hbm_paged", 2, cases.TD)


def _only(findings, rule):
    """Assert the given rule fired exactly once; return that finding."""
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) == 1, (rule, [f.message for f in findings])
    return hits[0]


def _rules(findings):
    return {f.rule for f in findings}


# ===================================================== contract passes ==

def test_contracts_clean_sweep():
    """The shipped registry passes the full contract sweep."""
    assert contracts.run_contracts() == []


def test_dma_unpaired_start_fires(monkeypatch):
    """stage_in that starts its copy but never waits -> one finding."""
    def bad_stage_in(self, i):
        sm = self.meta.states[i]
        sem = self._scr[sm.sem_idx].at[self.meta.depth]
        cp = stream_fused._async_copy(
            self._read_view(i, self.blk), self._scr[sm.scr_idx], sem,
            op="stage_in", state=i)
        cp.start()  # wait() dropped: the DMA is in flight at slot reuse

    monkeypatch.setattr(stream_fused._Engine, "stage_in", bad_stage_in)
    findings = contracts.run_contracts(points=[PAGED_STACKED])
    f = _only(findings, "dma-unpaired-start")
    assert "stage_in" in f.message and "never waited" in f.message


def test_dma_ring_order_fires(monkeypatch):
    """A ring that eagerly starts every window reuses slots while their
    previous copy is outstanding (visible at depth < n_windows)."""
    def bad_paged_fill(self, i, fill):
        sm = self.meta.states[i]
        ring, sems = self._scr[sm.ring_idx], self._scr[sm.sem_idx]
        depth, n_win, dmas = self.meta.depth, self.n_dblocks, {}
        for w in range(n_win):  # all upfront: slot w%depth reused hot
            dma = stream_fused._async_copy(
                self._read_view(i, pl.ds(w * self.td, self.td)),
                ring.at[w % depth], sems.at[w % depth],
                op="ring", state=i, window=w, slot=w % depth)
            dma.start()
            dmas[w] = dma
        for w in range(n_win):
            dmas.pop(w).wait()
            fill(w, pl.ds(w * self.td, self.td), ring.at[w % depth])

    monkeypatch.setattr(stream_fused._Engine, "paged_fill", bad_paged_fill)
    findings = contracts.run_contracts(
        points=[Point("stacked", "hbm_paged", 1, cases.TD)])
    f = _only(findings, "dma-ring-order")
    assert "still outstanding" in f.message


def test_dma_missing_site_fires(monkeypatch):
    """A paged state whose write-back never happens -> one finding."""
    monkeypatch.setattr(stream_fused._Engine, "write_back",
                        lambda self, i: None)
    findings = contracts.run_contracts(points=[PAGED_STACKED])
    f = _only(findings, "dma-missing-site")
    assert "write_back" in f.message


def test_hbm_alias_coverage_fires():
    """A captured paged launch with its aliases stripped -> one finding
    per unaliased state (stacked declares exactly one)."""
    (_, launch), = contracts.trace_point(PAGED_STACKED).launches
    doctored = dataclasses.replace(launch, aliases={})
    f = _only(contracts._check_launch(PAGED_STACKED, doctored),
              "hbm-alias-coverage")
    assert "not aliased" in f.message


def test_vmem_bytes_drift_fires():
    """Extra VMEM scratch the estimator does not know about -> drift."""
    (_, launch), = contracts.trace_point(PAGED_STACKED).launches
    doctored = dataclasses.replace(
        launch, scratch=[*launch.scratch,
                         pltpu.VMEM((8, 128), jnp.float32)])
    f = _only(contracts._check_launch(PAGED_STACKED, doctored),
              "vmem-bytes-drift")
    assert "stream_vmem_bytes" in f.message


def test_pingpong_parity_fires(monkeypatch):
    """A final-plane select decoupled from the write parity -> finding."""
    monkeypatch.setattr(stream_fused, "paged_final_plane", lambda t: 0)
    f = _only(contracts.check_parity_helpers(), "pingpong-parity")
    assert "final-plane" in f.message


def test_static_zero_states_fires():
    """A 'static' CellSpec that smuggles StateDefs past registration."""
    spec = dataclasses.replace(stream_fused.REGISTRY["gcrn"],
                               temporal="static")
    f = _only(contracts.check_registry_declarations({"fake_static": spec}),
              "static-zero-states")
    assert "fake_static" in f.message


def test_launch_assembly_error_fires():
    """A registered family without an analysis fixture IS a finding."""
    findings = contracts.run_contracts(
        registry={"mystery": stream_fused.REGISTRY["gcrn"]},
        points=[Point("mystery", "vmem", None, None)])
    f = _only(findings, "launch-assembly-error")
    assert "mystery" in f.message


# ========================================================== lint rules ==

def _snippet(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return rel


def _lint_one(tmp_path, rel, src, rule):
    findings = lint.run_lint(tmp_path,
                             files=[_snippet(tmp_path, rel, src)])
    return _only(findings, rule)


def test_stream_def_outside_registry_fires(tmp_path):
    f = _lint_one(tmp_path, "src/repro/rogue.py", """\
        def my_gcrn_stream_launcher(x):
            return x
        """, "stream-def-outside-registry")
    assert "my_gcrn_stream_launcher" in f.message and f.line == 1


def test_stream_def_ref_oracles_exempt(tmp_path):
    rel = _snippet(tmp_path, "src/repro/kernels/oracles.py", """\
        def gcrn_stream_ref(x):
            return x
        """)
    assert lint.run_lint(tmp_path, files=[rel]) == []


def test_single_kernel_body_fires(tmp_path):
    f = _lint_one(tmp_path, "src/repro/kernels/stream_fused.py", """\
        def first_kernel(x_ref):
            pass

        def second_kernel(y_ref):
            pass
        """, "single-kernel-body")
    assert "found 2" in f.message and f.line == 4


def test_mode_string_dispatch_fires(tmp_path):
    f = _lint_one(tmp_path, "examples/demo.py", """\
        run_stream(snaps, mode="v3")
        """, "mode-string-dispatch")
    assert 'mode="v3"' in f.message


def test_direct_stream_steps_fires(tmp_path):
    f = _lint_one(tmp_path, "benchmarks/bench.py", """\
        outs = ops.stream_steps(fam, *args)
        """, "direct-stream-steps")
    assert "stream_steps" in f.message


def test_broad_except_fires(tmp_path):
    f = _lint_one(tmp_path, "src/repro/fragile.py", """\
        try:
            launch()
        except Exception:
            pass
        """, "broad-except")
    assert "except Exception" in f.message and f.line == 3


def test_broad_except_allowlist_skipped(tmp_path):
    rel = _snippet(tmp_path, "src/repro/serve/engine.py", """\
        try:
            launch()
        except Exception:
            pass
        """)
    assert lint.run_lint(tmp_path, files=[rel]) == []


def test_mutable_default_arg_fires(tmp_path):
    f = _lint_one(tmp_path, "src/repro/leaky.py", """\
        def accumulate(x, seen=[]):
            seen.append(x)
            return seen
        """, "mutable-default-arg")
    assert "accumulate" in f.message


def test_jnp_in_kernel_body_fires(tmp_path):
    f = _lint_one(tmp_path, "src/repro/kernels/extra.py", """\
        def fancy_kernel(x_ref, o_ref):
            o_ref[...] = jnp.concatenate([x_ref[...], x_ref[...]])
        """, "jnp-in-kernel-body")
    assert "jnp.concatenate" in f.message and f.severity == "warning"


@pytest.mark.parametrize("body", [
    "def _x_cell(eng, ins, outs, scr):\n"
    "    return jnp.take(ins[0][...], ins[1][...], axis=0)\n",
    "class _Engine:\n"
    "    def scatter(self, store, rows, val):\n"
    "        return store.at[rows].set(val, mode='drop')\n",
])
def test_gather_scatter_in_stream_engine_fires(tmp_path, body):
    """The stream engine's kernel bodies (cell hooks, ``_Engine``
    methods) may not gather or ``.at[]``-update a value."""
    f = _lint_one(tmp_path, "src/repro/kernels/stream_fused.py", body,
                  "jnp-in-kernel-body")
    assert "take" in f.message or ".at[...].set" in f.message


def test_gather_outside_stream_engine_allowed(tmp_path):
    """jnp.take stays legal in other kernel modules' bodies."""
    rel = _snippet(tmp_path, "src/repro/kernels/other.py", """\
        def _x_cell(eng, ins, outs, scr):
            return jnp.take(ins[0][...], ins[1][...], axis=0)
        """)
    assert lint.run_lint(tmp_path, files=[rel]) == []


def test_jnp_outside_kernel_body_allowed(tmp_path):
    rel = _snippet(tmp_path, "src/repro/kernels/host.py", """\
        def pad_host(x):
            return jnp.concatenate([x, x])
        """)
    assert lint.run_lint(tmp_path, files=[rel]) == []


def test_syntax_error_fires(tmp_path):
    f = _lint_one(tmp_path, "src/repro/broken.py", """\
        def f(:
        """, "syntax-error")
    assert "unparseable" in f.message


def test_suppression_comment_waives(tmp_path):
    rel = _snippet(tmp_path, "src/repro/fragile.py", """\
        try:
            launch()
        except Exception:  # booster: ignore[broad-except]
            pass
        """)
    findings = lint.run_lint(tmp_path, files=[rel])
    assert _rules(findings) == {"broad-except"}
    report = core.Report()
    kept = core.apply_suppressions(findings, tmp_path, report)
    assert kept == [] and report.suppressed == 1


# ========================================================= drift rules ==

def test_plan_doc_drift_fires(tmp_path):
    """Un-backticking one field's table row de-documents it."""
    text = (core.repo_root() / "docs/api.md").read_text()
    assert "| `fault_plan` |" in text
    (tmp_path / "api.md").write_text(
        text.replace("| `fault_plan` |", "| fault_plan |"))
    f = _only(drift.check_plan_docs(tmp_path, api_md="api.md"),
              "plan-doc-drift")
    assert "`fault_plan`" in f.message and "no row" in f.message


def test_family_levels_drift_fires():
    fake = {**stream_fused.REGISTRY,
            "novel": stream_fused.REGISTRY["gcrn"]}
    f = _only(drift.check_family_levels(registry=fake),
              "family-levels-drift")
    assert "novel" in f.message


def test_ci_matrix_drift_fires():
    fake = {**stream_fused.REGISTRY,
            "novel": stream_fused.REGISTRY["gcrn"]}
    f = _only(drift.check_ci_matrix(core.repo_root(), registry=fake),
              "ci-matrix-drift")
    assert "novel" in f.message


def test_harness_case_drift_fires():
    """Both case-builder artifacts (tests/harness.py and the analyzer's
    own fixtures) must cover a newly registered family — one finding
    each."""
    fake = {**stream_fused.REGISTRY,
            "novel": stream_fused.REGISTRY["gcrn"]}
    findings = drift.check_harness_cases(core.repo_root(), registry=fake)
    assert [f.rule for f in findings] == ["harness-case-drift"] * 2
    assert {f.path for f in findings} == {"tests/harness.py",
                                          "src/repro/analysis/cases.py"}


def test_drift_clean_tree():
    assert drift.run_drift(core.repo_root()) == []


# ================================================= CLI / whole-analyzer ==

def test_rule_ids_unique_across_groups():
    total = (len(contracts.RULES) + len(lint.RULES) + len(drift.RULES))
    assert len(analysis.ALL_RULES) == total
    for rid, r in analysis.ALL_RULES.items():
        assert rid == r.id and r.group in core.GROUPS
        assert r.severity in ("error", "warning")


def test_select_rules():
    ids = core.select_rules(analysis.ALL_RULES, "lint,plan-doc-drift")
    assert "broad-except" in ids and "plan-doc-drift" in ids
    assert "dma-ring-order" not in ids
    with pytest.raises(SystemExit):
        core.select_rules(analysis.ALL_RULES, "no-such-rule")


def test_clean_tree_and_stable_json():
    """Acceptance: the shipped tree is analyzer-clean with ZERO
    suppressions, and the JSON report is byte-stable across runs."""
    r1 = analysis.run_all()
    assert r1.findings == [] and r1.suppressed == 0
    r2 = analysis.run_all()
    assert r1.to_json() == r2.to_json()
    assert '"findings": []' in r1.to_json()


def test_cli_exit_codes(tmp_path, capsys):
    from repro.analysis.__main__ import main
    assert main(["--rules", "drift", "--format", "json"]) == 0
    out = capsys.readouterr().out
    assert '"version": 1' in out
    assert main(["--list-rules"]) == 0
