"""Repo AST lint (stdlib ``ast``, no new deps) — the "lint" pass group.

Subsumes the four grep-based CI guards (family-named stream kernels
outside the registry module, the single-kernel-body count, raw
``mode="vN"`` dispatch, direct ``stream_steps`` calls) as real syntax
rules, and adds the hygiene rules greps could not express: bare/overbroad
``except`` outside the allowlisted supervision sites, mutable default
arguments, and ``jnp`` ops inside Pallas kernel bodies that have no TPU
lowering (or a strictly better ``lax``/indexing form).

Rule anatomy: every rule is a function ``(relpath, tree, lines) ->
[Finding]`` registered in ``RULES``/``CHECKS`` with a severity and a
rationale (rendered by docs/static_analysis.md). Suppress a single
finding with ``# booster: ignore[rule-id]`` on its line — the shipped
tree carries zero suppressions, and tests/test_analysis.py pins that
every rule fires on an injected violation.
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Optional

from repro.analysis.core import Finding, Rule

#: directories scanned by default, relative to the repo root. tests/ is
#: deliberately out of scope: the deprecated mode-string shims are pinned
#: there on purpose.
LINT_ROOTS = ("src", "examples", "benchmarks")

#: supervision sites where catching ``Exception``/``BaseException`` is
#: the point (tenant fault isolation, suite harness catch-and-report) —
#: the broad-except rule skips these files entirely. Everything else
#: must catch the specific expected errors.
BROAD_EXCEPT_ALLOWLIST = frozenset({
    "src/repro/serve/engine.py",   # tenant supervision / producer shutdown
    "benchmarks/run.py",           # suite harness: record failure, exit 1
    "src/repro/analysis/contracts.py",  # sweep: any trace failure -> finding
})

#: the registry module that owns the one stream-engine kernel body.
STREAM_FUSED = "src/repro/kernels/stream_fused.py"

#: family-named stream def pattern (the old CI grep, as a name match) and
#: its oracle exemption (kernels/ref.py ``*_stream*_ref[s]`` functions).
_FAMILY_STREAM = re.compile(
    r"^_?[a-z_]*(gcrn|stacked|evolve|dgnn|tgn|static)[a-z_]*_stream[a-z_]*$")
_REF_SUFFIX = re.compile(r"_refs?$")

_KERNEL_DEF = re.compile(r"^[a-z_]*_kernel$")

#: ``jnp`` ops with no Pallas TPU lowering or a strictly better in-kernel
#: form (``lax`` scans/concats, explicit static slices): shape
#: restructuring and data-dependent ops. Element-wise math, ``jnp.dot``,
#: ``jnp.take`` and friends lower fine and stay allowed.
JNP_KERNEL_DENYLIST = frozenset({
    "einsum", "sort", "argsort", "unique", "nonzero", "cumsum", "cumprod",
    "pad", "concatenate", "stack", "tile", "repeat", "roll", "split",
    "moveaxis", "append", "delete", "insert", "resize",
})

#: further ops barred from the stream engine's kernel bodies (the cell
#: hooks and the ``_Engine`` methods of kernels/stream_fused.py): a gather
#: or an ``.at[...]`` update over a VMEM value has no Mosaic lowering —
#: the engine aggregates with ``_ell_matrix`` matmuls and moves global
#: store rows with scalar-indexed row loops instead.
STREAM_ENGINE_DENYLIST = frozenset({"take", "take_along_axis"})
_AT_UPDATES = frozenset({"set", "add", "multiply", "divide", "power",
                         "min", "max", "apply", "get"})

RULES = {r.id: r for r in (
    Rule("stream-def-outside-registry", "lint", "error",
         "Family code lives in stream_fused.REGISTRY as declarative cell "
         "specs; a family-named stream kernel/launcher anywhere else in "
         "src/ resurrects the pre-registry copy-paste (XLA oracles named "
         "*_stream*_ref are exempt)."),
    Rule("single-kernel-body", "lint", "error",
         "kernels/stream_fused.py owns exactly ONE Pallas kernel body "
         "(_stream_engine_kernel): the generic-framework claim is that "
         "families differ only in cell specs, never in kernel bodies."),
    Rule("mode-string-dispatch", "lint", "error",
         "Surface code (examples/, benchmarks/, src/repro/serve/) goes "
         "through typed StreamPlans; raw mode=\"vN\" dataflow dispatch is "
         "confined to the deprecated shims and the plan executors."),
    Rule("direct-stream-steps", "lint", "error",
         "Direct ops.stream_steps[_batched] calls bypass plan validation; "
         "surface code uses api.run_arrays / BoosterSession instead."),
    Rule("broad-except", "lint", "error",
         "Bare ``except:`` or ``except (Base)Exception`` hides real bugs "
         "(including the paged-DMA contract errors stream_call raises). "
         "Catch the specific expected errors; only the allowlisted "
         "supervision sites may catch everything."),
    Rule("mutable-default-arg", "lint", "error",
         "A mutable default ([] / {} / set()) is shared across calls — "
         "state leaks between launches. Use None (or a tuple) and "
         "construct inside the function."),
    Rule("jnp-in-kernel-body", "lint", "warning",
         "Inside a Pallas kernel body, shape-restructuring / "
         "data-dependent jnp ops (einsum, concatenate, sort, cumsum, …) "
         "either fail to lower on TPU or hide a relayout; use lax "
         "equivalents or static slices on the host side. The stream "
         "engine's kernel bodies also may not gather (jnp.take) or "
         "update a value through .at[...]: neither lowers on the chip."),
    Rule("syntax-error", "lint", "error",
         "A file in the lint scope failed to parse — nothing else can be "
         "checked until it does."),
)}


def _iter_files(root: Path, files=None):
    """Yield (relpath, source) for the lint scope. ``files`` overrides
    discovery (tests inject single-snippet trees)."""
    if files is not None:
        paths = [Path(f) for f in files]
    else:
        paths = []
        for top in LINT_ROOTS:
            base = root / top
            if base.is_dir():
                paths.extend(sorted(base.rglob("*.py")))
    for p in paths:
        p = p if p.is_absolute() else root / p
        if "__pycache__" in p.parts:
            continue
        try:
            yield p.relative_to(root).as_posix(), p.read_text()
        except (OSError, ValueError):
            continue


def _is_kernel_body(fn: ast.FunctionDef) -> bool:
    """Heuristic for Pallas kernel bodies / engine hooks: a parameter
    named ``eng``/``refs`` or ending in ``_ref(s)``, or a ``*_kernel`` /
    ``*_cell`` function name."""
    names = [a.arg for a in (fn.args.posonlyargs + fn.args.args
                             + fn.args.kwonlyargs)]
    if fn.args.vararg:
        names.append(fn.args.vararg.arg)
    if any(n in ("eng", "refs") or _REF_SUFFIX.search(n) for n in names):
        return True
    return fn.name.endswith("_kernel") or fn.name.endswith("_cell")


def _find(rule: str, path: str, node, msg: str) -> Finding:
    r = RULES[rule]
    return Finding(rule, r.group, r.severity, path,
                   getattr(node, "lineno", 0), msg)


# ------------------------------------------------------------------ rules

def _chk_stream_def(path, tree, lines):
    if not path.startswith("src/") or path == STREAM_FUSED:
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _FAMILY_STREAM.match(node.name) and not _REF_SUFFIX.search(node.name):
            out.append(_find(
                "stream-def-outside-registry", path, node,
                f"family-named stream def `{node.name}` outside "
                f"{STREAM_FUSED} — register a cell spec instead"))
    return out


def _chk_single_kernel(path, tree, lines):
    if path != STREAM_FUSED:
        return []
    kernels = [n for n in tree.body
               if isinstance(n, ast.FunctionDef) and _KERNEL_DEF.match(n.name)]
    if len(kernels) == 1:
        return []
    anchor = kernels[1] if len(kernels) > 1 else tree
    names = [k.name for k in kernels] or ["<none>"]
    return [_find("single-kernel-body", path, anchor,
                  f"expected exactly 1 stream-engine kernel body, found "
                  f"{len(kernels)}: {', '.join(names)}")]


_SERVE_SCOPE = ("examples/", "benchmarks/", "src/repro/serve/")


def _chk_mode_string(path, tree, lines):
    if not path.startswith(_SERVE_SCOPE):
        return []

    def _is_vn(node):
        return (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and re.fullmatch(r"v[0-9]+", node.value))

    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "mode" and _is_vn(kw.value):
                    out.append(_find(
                        "mode-string-dispatch", path, kw.value,
                        f'raw mode="{kw.value.value}" dispatch — build a '
                        "StreamPlan (api.plan) instead"))
        elif isinstance(node, ast.Assign):
            if (any(isinstance(t, ast.Name) and t.id == "mode"
                    for t in node.targets) and _is_vn(node.value)):
                out.append(_find(
                    "mode-string-dispatch", path, node,
                    f'mode = "{node.value.value}" assignment — build a '
                    "StreamPlan (api.plan) instead"))
    return out


def _chk_stream_steps(path, tree, lines):
    if not path.startswith(_SERVE_SCOPE):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (fn.id if isinstance(fn, ast.Name)
                else fn.attr if isinstance(fn, ast.Attribute) else None)
        if name in ("stream_steps", "stream_steps_batched"):
            out.append(_find(
                "direct-stream-steps", path, node,
                f"direct {name}() call outside the plan executors — use "
                "api.run_arrays(plan(...), *arrays)"))
    return out


def _chk_broad_except(path, tree, lines):
    if path in BROAD_EXCEPT_ALLOWLIST:
        return []

    def _broad(expr) -> Optional[str]:
        if expr is None:
            return "bare except:"
        if isinstance(expr, ast.Name) and expr.id in ("Exception",
                                                      "BaseException"):
            return f"except {expr.id}"
        if isinstance(expr, ast.Tuple):
            for e in expr.elts:
                b = _broad(e)
                if b and b != "bare except:":
                    return b
        return None

    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler):
            b = _broad(node.type)
            if b:
                out.append(_find(
                    "broad-except", path, node,
                    f"{b} outside the supervision allowlist — catch the "
                    "specific expected errors (and log what was caught)"))
    return out


def _chk_mutable_default(path, tree, lines):
    def _mutable(node) -> bool:
        if isinstance(node, (ast.List, ast.Dict, ast.Set,
                             ast.ListComp, ast.DictComp, ast.SetComp)):
            return True
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in ("list", "dict", "set"))

    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            continue
        name = getattr(node, "name", "<lambda>")
        for d in list(node.args.defaults) + [d for d in node.args.kw_defaults
                                             if d is not None]:
            if _mutable(d):
                out.append(_find(
                    "mutable-default-arg", path, d,
                    f"mutable default argument in `{name}` — shared "
                    "across calls; default to None/() instead"))
    return out


def _engine_methods(tree) -> set:
    """The ``_Engine`` methods of the stream-engine module: they run
    inside the kernel on behalf of every cell."""
    return {id(fn) for cls in ast.walk(tree)
            if isinstance(cls, ast.ClassDef) and cls.name == "_Engine"
            for fn in cls.body if isinstance(fn, ast.FunctionDef)}


def _at_update(node) -> Optional[str]:
    """``x.at[...].<op>(...)`` -> op, else None."""
    fn = node.func
    if (isinstance(fn, ast.Attribute) and fn.attr in _AT_UPDATES
            and isinstance(fn.value, ast.Subscript)
            and isinstance(fn.value.value, ast.Attribute)
            and fn.value.value.attr == "at"):
        return fn.attr
    return None


def _chk_jnp_in_kernel(path, tree, lines):
    if not path.startswith("src/repro/kernels/"):
        return []
    engine = path == STREAM_FUSED
    methods = _engine_methods(tree) if engine else set()
    deny = JNP_KERNEL_DENYLIST | (STREAM_ENGINE_DENYLIST if engine
                                  else frozenset())
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef) or not (
                _is_kernel_body(fn) or id(fn) in methods):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if (isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "jnp"
                    and node.func.attr in deny):
                out.append(_find(
                    "jnp-in-kernel-body", path, node,
                    f"jnp.{node.func.attr} inside kernel body "
                    f"`{fn.name}` — no TPU Pallas lowering / hides a "
                    "relayout; use the lax equivalent or hoist host-side"))
            elif engine and _at_update(node):
                out.append(_find(
                    "jnp-in-kernel-body", path, node,
                    f".at[...].{_at_update(node)} inside kernel body "
                    f"`{fn.name}` — no TPU Pallas lowering; move rows "
                    "with the engine's scatter_tile row loop"))
    return out


CHECKS = (_chk_stream_def, _chk_single_kernel, _chk_mode_string,
          _chk_stream_steps, _chk_broad_except, _chk_mutable_default,
          _chk_jnp_in_kernel)


def run_lint(root: Path, files=None, rules: Optional[frozenset] = None):
    """Run the lint rules over the repo (or an injected file list).
    Returns raw findings — suppression filtering happens in core."""
    findings = []
    for relpath, source in _iter_files(root, files):
        try:
            tree = ast.parse(source, filename=relpath)
        except SyntaxError as e:
            f = Finding("syntax-error", "lint", "error", relpath,
                        e.lineno or 0, f"unparseable file: {e.msg}")
            if rules is None or f.rule in rules:
                findings.append(f)
            continue
        lines = source.splitlines()
        for chk in CHECKS:
            found = chk(relpath, tree, lines)
            if rules is not None:
                found = [f for f in found if f.rule in rules]
            findings.extend(found)
    return findings
