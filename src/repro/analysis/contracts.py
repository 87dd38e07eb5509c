"""Kernel-contract passes — static verification of the stream engine's
launch/DMA protocol, with a recording shim and NO device execution.

Every ``(family, residency, buffer_depth, td)`` point of the registry is
driven through launch assembly and kernel TRACING only: the pass installs
``stream_fused.set_trace_recorder`` and abstractly evaluates the launch
(``jax.eval_shape``), so the kernel body's Python-level paged protocol
(``stage_in`` / ``paged_fill`` / ``write_back``) runs and logs every
``pltpu.make_async_copy`` start/wait while no kernel ever executes.

Checked per point:
  * every DMA start has a matching wait before trace end, and stage-in /
    write-back stay synchronous pairs (``dma-unpaired-start``);
  * the read ring covers windows 0..D-1 in order and never reissues a
    ring slot while its previous copy is outstanding, under depths 1/2/4
    (``dma-ring-order``);
  * every paged state stages in and writes back — and a vmem launch
    issues no DMA at all (``dma-missing-site``);
  * every HBM-resident StateDef (and every ANY-memory-space input) is
    covered by ``input_output_aliases`` (``hbm-alias-coverage``);
  * ping-pong plane parity is consistent with the t grid axis: read/write
    planes alternate, step t reads what t-1 wrote starting from plane 0,
    the host-side final-plane select matches the simulated write parity,
    and paged plane pairs carry the right plane count
    (``pingpong-parity``);
  * the plan-time ``stream_vmem_bytes`` estimate equals the assembled
    VMEM scratch byte-exact (``vmem-bytes-drift``);
  * ``static`` temporal families declare zero StateDefs, no evolve hook,
    no aliases (``static-zero-states``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax

from repro.analysis import cases
from repro.analysis.core import Finding, Rule
from repro.kernels import ops, stream_fused

STREAM_FUSED_PATH = "src/repro/kernels/stream_fused.py"

RULES = {r.id: r for r in (
    Rule("dma-unpaired-start", "contracts", "error",
         "A make_async_copy start without a matching wait leaves the DMA "
         "in flight when its buffer/semaphore is reused — data races on "
         "real hardware that interpret-mode tests cannot see."),
    Rule("dma-ring-order", "contracts", "error",
         "The depth-buffered read ring must sweep windows in order and "
         "never restart a ring slot whose previous copy is outstanding "
         "(wait(w) strictly before start(w+depth))."),
    Rule("dma-missing-site", "contracts", "error",
         "Every paged state must stage in and write back exactly its "
         "window per program; a vmem launch must issue no DMA at all."),
    Rule("hbm-alias-coverage", "contracts", "error",
         "A paged store lives in HBM only via input_output_aliases; an "
         "unaliased ANY-space state input silently doubles HBM traffic "
         "and breaks evolve-in-place semantics."),
    Rule("pingpong-parity", "contracts", "error",
         "Read plane t%2 / write plane 1-t%2 / final plane after T steps "
         "must form one consistent parity scheme anchored at plane 0 — "
         "an off-by-one returns the stale state plane."),
    Rule("vmem-bytes-drift", "contracts", "error",
         "plan()'s stream_vmem_bytes budget check is only sound if it "
         "matches the assembled launch's VMEM scratch byte-exact."),
    Rule("static-zero-states", "contracts", "error",
         "The 'static' temporal contract means zero StateDefs, no evolve "
         "hook, nothing aliased — recurrence without declared state "
         "breaks serve checkpointing and the express lane."),
    Rule("launch-assembly-error", "contracts", "error",
         "A registry point that fails to assemble (or has no analysis "
         "fixture) cannot be verified — the point itself is the finding."),
)}


@dataclass(frozen=True)
class Point:
    """One contract-sweep coordinate."""

    family: str
    residency: str
    depth: Optional[int]
    td: Optional[int]

    def label(self) -> str:
        tag = f"{self.family}/{self.residency}/td={self.td}"
        return tag if self.depth is None else f"{tag}/depth={self.depth}"


def registry_points(registry=None):
    """The full sweep: both vmem blockings for every family, plus every
    legal buffer depth under hbm_paged for stateful families."""
    registry = stream_fused.REGISTRY if registry is None else registry
    pts = []
    for family in sorted(registry):
        spec = registry[family]
        pts.append(Point(family, "vmem", None, None))
        pts.append(Point(family, "vmem", None, cases.TD))
        if spec.temporal != "static":
            for depth in stream_fused.BUFFER_DEPTHS:
                pts.append(Point(family, "hbm_paged", depth, cases.TD))
    return pts


class LaunchRecorder:
    """The recording shim stream_fused's trace hooks feed."""

    def __init__(self):
        self.launches = []
        self.events = []

    def launch(self, family, launch):
        self.launches.append((family, launch))

    def dma(self, event, **tag):
        self.events.append({"event": event, **tag})


def trace_point(point: Point, registry=None) -> LaunchRecorder:
    """Assemble + trace one sweep point under the recorder. Abstract
    evaluation only — no kernel executes, no buffers materialize."""
    args = cases.stream_args(point.family)
    kw = dict(tn=cases.TN, td=point.td)
    if point.residency == "hbm_paged":
        kw.update(state_residency="hbm_paged", buffer_depth=point.depth)
    rec = LaunchRecorder()
    prev = stream_fused.set_trace_recorder(rec)
    stream_fused.stream_call.clear_cache()
    try:
        jax.eval_shape(lambda: ops.stream_steps(point.family, *args, **kw))
    finally:
        stream_fused.set_trace_recorder(prev)
        stream_fused.stream_call.clear_cache()
    return rec


def _find(rule: str, msg: str, path: str = STREAM_FUSED_PATH,
          line: int = 0) -> Finding:
    r = RULES[rule]
    return Finding(rule, r.group, r.severity, path, line, msg)


# ---------------------------------------------------------- sub-checks --

def check_registry_declarations(registry=None):
    """static families declare zero StateDefs / no state-less recurrence
    (re-checked here so an injected spec that bypassed import-time
    validation still surfaces)."""
    registry = stream_fused.REGISTRY if registry is None else registry
    out = []
    for family in sorted(registry):
        spec = registry[family]
        if spec.temporal == "static" and spec.states:
            out.append(_find(
                "static-zero-states",
                f"static family {family!r} declares StateDefs "
                f"{[s.name for s in spec.states]} — the static contract "
                "is zero recurrent state"))
    return out


def check_parity_helpers():
    """Simulate a stream through the exported parity helpers: step t must
    read the plane step t-1 wrote (anchored at plane 0), and the
    host-side final-plane select must land on the simulated final plane."""
    sf = stream_fused
    out = []
    plane = 0  # builds stack [state0, zeros]: plane 0 holds the t=0 read
    for t in range(8):
        r, w = sf.paged_read_plane(t), sf.paged_write_plane(t)
        if r != plane or w == r or w not in (0, 1):
            out.append(_find(
                "pingpong-parity",
                f"paged plane chain breaks at t={t}: read_plane={r} "
                f"write_plane={w} but the live state sits in plane "
                f"{plane}"))
            break
        plane = w
    plane = 0
    for t_steps in range(1, 9):
        plane = sf.paged_write_plane(t_steps - 1)
        if sf.paged_final_plane(t_steps) != plane:
            out.append(_find(
                "pingpong-parity",
                f"host-side final-plane select disagrees with the "
                f"simulated write parity at T={t_steps}: "
                f"paged_final_plane={sf.paged_final_plane(t_steps)}, "
                f"last write plane={plane}"))
            break
    return out


def _check_launch(point: Point, launch) -> list:
    """Alias coverage, plane counts, scratch-byte estimate, static
    emptiness — all static properties of the assembled _Launch."""
    out = []
    meta = launch.meta
    lbl = point.label()
    spec_states = {sm.in_idx: sm for sm in meta.states}

    if meta.temporal == "static":
        if meta.states or launch.evolve is not None or launch.aliases:
            out.append(_find(
                "static-zero-states",
                f"{lbl}: static launch carries states="
                f"{len(meta.states)}, evolve={launch.evolve is not None}, "
                f"aliases={dict(launch.aliases)}"))

    if meta.paged:
        for sm in meta.states:
            if launch.aliases.get(sm.in_idx) != sm.out_idx:
                out.append(_find(
                    "hbm-alias-coverage",
                    f"{lbl}: paged state (kind={sm.kind}, input "
                    f"{sm.in_idx}) is not aliased onto output "
                    f"{sm.out_idx} — the HBM store would not evolve "
                    "in place"))
        for idx, spec in enumerate(launch.in_specs):
            is_any = getattr(spec, "memory_space", None) is stream_fused.pl.ANY
            if is_any and idx not in spec_states and idx not in launch.aliases:
                out.append(_find(
                    "hbm-alias-coverage",
                    f"{lbl}: ANY-memory-space input {idx} is neither a "
                    "declared state nor aliased to an output"))
        # plane-count layout of the HBM pair must match the state kind
        for sm in meta.states:
            shape = launch.out_shape[sm.out_idx].shape
            want = {"pingpong": 2, "row": 1}.get(sm.kind)
            if want is not None and shape[1] != want:
                out.append(_find(
                    "pingpong-parity",
                    f"{lbl}: {sm.kind} state output carries {shape[1]} "
                    f"plane(s), expected {want} (shape {shape})"))
    elif launch.aliases:
        out.append(_find(
            "hbm-alias-coverage",
            f"{lbl}: vmem launch declares aliases {dict(launch.aliases)} "
            "— in-place aliasing is a paged-residency contract"))

    dims = _launch_dims(point.family, launch)
    if dims is not None:
        est = stream_fused.stream_vmem_bytes(
            point.family, td=meta.td, residency=point.residency,
            depth=meta.depth, **dims)
        got = stream_fused.launch_scratch_bytes(launch)
        if est != got:
            out.append(_find(
                "vmem-bytes-drift",
                f"{lbl}: stream_vmem_bytes estimates {est} B but the "
                f"assembled launch allocates {got} B of VMEM scratch — "
                "plan()'s budget check is lying"))
    return out


def _launch_dims(family: str, launch):
    """Recover the stream_vmem_bytes inputs from the assembled launch
    (grid + shapes), not from the fixture — so the check also covers the
    ops-level padding between fixture and launch."""
    meta = launch.meta
    out0 = launch.out_shape[0].shape          # (B, T, n_pad, d_pad)
    dims = dict(g_rows=meta.g_rows, n_pad=out0[2], d_pad=out0[3],
                n_layers=launch.grid[2], tn=meta.tn, din=0, dmid=0)
    ins = launch.inputs
    if family in ("gcrn", "stacked"):
        dims["din"] = ins[2].shape[3]
        if family == "stacked":
            dims["dmid"] = ins[6].shape[1]
    elif family == "tgn":
        dims["din"] = ins[3].shape[3]
    elif family not in ("evolve", "static_gcn"):
        return None  # unknown family: no estimator formula to check
    return dims


def _check_dma(point: Point, launch, events) -> list:
    """Replay the recorded start/wait stream against the protocol."""
    out = []
    meta = launch.meta
    lbl = point.label()
    if not meta.paged:
        if events:
            out.append(_find(
                "dma-missing-site",
                f"{lbl}: vmem launch issued {len(events)} DMA event(s) — "
                "resident layouts must not touch the DMA engine"))
        return out

    n_win = launch.grid[3]
    outstanding = {}
    ring_started, ring_waited = {}, {}
    for ev in events:
        key = (ev["op"], ev["state"], ev.get("slot"))
        if ev["event"] == "start":
            if key in outstanding:
                rule = ("dma-ring-order" if ev["op"] == "ring"
                        else "dma-unpaired-start")
                out.append(_find(
                    rule,
                    f"{lbl}: {ev['op']} DMA re-started on state "
                    f"{ev['state']} slot {ev.get('slot')} (window "
                    f"{ev.get('window')}) while the previous copy is "
                    "still outstanding"))
            outstanding[key] = ev
            if ev["op"] == "ring":
                ring_started.setdefault(ev["state"], []).append(ev["window"])
        else:
            if key not in outstanding:
                out.append(_find(
                    "dma-unpaired-start",
                    f"{lbl}: {ev['op']} DMA wait on state {ev['state']} "
                    f"slot {ev.get('slot')} with no outstanding start"))
            outstanding.pop(key, None)
            if ev["op"] == "ring":
                ring_waited.setdefault(ev["state"], []).append(ev["window"])
    for key, ev in outstanding.items():
        out.append(_find(
            "dma-unpaired-start",
            f"{lbl}: {ev['op']} DMA started on state {ev['state']} slot "
            f"{ev.get('slot')} but never waited before trace end"))

    by_state_op = {}
    for ev in events:
        by_state_op.setdefault((ev["state"], ev["op"]), []).append(ev)
    for i, sm in enumerate(meta.states):
        for op in ("stage_in", "write_back"):
            if not by_state_op.get((i, op)):
                out.append(_find(
                    "dma-missing-site",
                    f"{lbl}: paged state {i} (kind={sm.kind}) never "
                    f"issued a {op} DMA — the HBM store and VMEM "
                    "staging window would desynchronize"))
        if sm.ring_idx >= 0:
            started = ring_started.get(i, [])
            waited = ring_waited.get(i, [])
            if sorted(started) != list(range(n_win)):
                out.append(_find(
                    "dma-ring-order",
                    f"{lbl}: ring sweep of state {i} started windows "
                    f"{started}, expected every window 0..{n_win - 1} "
                    "exactly once"))
            if waited != sorted(waited) or sorted(waited) != list(range(n_win)):
                out.append(_find(
                    "dma-ring-order",
                    f"{lbl}: ring sweep of state {i} waited windows "
                    f"{waited} — windows must complete in order "
                    f"0..{n_win - 1}"))
    return out


def run_contracts(registry=None, points=None,
                  rules: Optional[frozenset] = None) -> list:
    """The full contract pass: registry declarations, parity helpers,
    then every sweep point through the recording shim."""
    registry = stream_fused.REGISTRY if registry is None else registry
    findings = list(check_registry_declarations(registry))
    findings += check_parity_helpers()
    pts = registry_points(registry) if points is None else points
    for point in pts:
        try:
            rec = trace_point(point, registry)
        except Exception as exc:  # any trace failure becomes a finding
            findings.append(_find(
                "launch-assembly-error",
                f"{point.label()}: launch assembly/trace failed: "
                f"{type(exc).__name__}: {exc}"))
            continue
        if not rec.launches:
            findings.append(_find(
                "launch-assembly-error",
                f"{point.label()}: no launch captured — dispatch "
                "bypassed stream_call (force-ref gate left on?)"))
            continue
        for family, launch in rec.launches:
            findings.extend(_check_launch(point, launch))
            findings.extend(_check_dma(point, launch, rec.events))
    if rules is not None:
        findings = [f for f in findings if f.rule in rules]
    return findings
