"""DGNN-Booster V3 stream engine: ONE time-fused kernel, per-family cell specs.

The paper's central claim is a *generic* accelerator framework: one hardware
template whose dataflows cover the discrete-time DGNN families, not one
bespoke design per model. This module is that template's TPU edition. A
single kernel body — ``_stream_engine_kernel`` — owns the stream protocol:

  * grid layout ``(B, T, L, d_pad//td, n_pad//tn)`` (stream batch, time,
    GNN layer, state-feature block, node tile), every axis "arbitrary"
    (sequential on one core) so the recurrent state in VMEM scratch is
    serially reused across streams by construction;
  * stream-boundary **init** (each stream loads its own state at its first
    program) and **drain** (each (l, d) window writes its final state block
    at the stream's last program);
  * **ping-pong plane parity** for neighbour-aggregated states (read the
    t-1 plane, write the t plane, swapped by t's parity — the V1
    ping-pong carry pushed down into the kernel);
  * **live-gating**: the between-snapshot weight-evolution hook only runs
    on live snapshots, so serve no-op tail padding never advances the
    recurrence;
  * **residency policy**: which tensors stay VMEM-resident across the T
    axis (node-state stores, evolving weights) vs stream per step;
  * **row traffic**: the renumber-table-guided gather of a step's node rows
    out of the global store and the scatter of updated rows back.

The DGNN families are *declarative cell specs* registered in ``REGISTRY``
— recurrent state tensors plus a per-step cell body (and, for the
weights-evolved family, a between-snapshot evolution hook). Callers
(kernels/ops.py, core/*.py, serve/engine.py) dispatch through the registry
via ``stream_call(family, ...)``; no family-named kernel exists.

Forms the TPU compiler lowers
-----------------------------
Everything inside the kernel body is written in forms Mosaic compiles for
a real chip, not only for the interpreter:

  * **ELL aggregation as a dense tile matmul.** A node tile's ELL lanes
    (local source ids + coefficients) become a ``(tn, n_src)`` aggregation
    matrix built with iota compares (``_ell_matrix``), multiplied into the
    step-local feature table at full f32 precision (``_dot_exact``) — the
    MXU does the gather; no vector gather is needed.
  * **Global-store rows by scalar id.** The per-node global row ids live in
    SMEM (one ``(1, n_pad)`` table per step); the engine copies one
    full-width row per loop iteration (``_Engine.gather_step`` /
    ``gather_tile`` / ``scatter_tile``), dropping the ``g_rows`` sentinel
    of padding rows. Neighbour aggregations over the t-1 store first
    gather the step's node rows into a local table, then aggregate that
    table with the tile matrix — the same values the renumbered per-step
    path gathers.
  * **Per-row vectors** (node masks) are ``(tn, 1)`` column blocks and
    scalar flags (EvolveGCN's live flag) a whole-array SMEM operand, so
    every VMEM block meets the (8, 128) tiling rule.

Row copies and output tiles address full state rows, so on the chip the
state window must span the whole feature width: D-blocked layouts
(``td < d_pad``) lower in interpret mode only (the compiler refuses a
dynamic row access at a column offset).

D-axis blocking (VMEM-oversized state stores)
---------------------------------------------
When the ``(n_global, hidden)`` state store exceeds VMEM, the hidden axis
is blocked onto the ``d`` grid dimension (``td`` columns per block). Cell
bodies address state exclusively through ``(n_global, td)`` column windows
— the paging unit of the HBM residency policy below — and the gate
weights are re-packed host-side into per-block gate tiles
``(D, rows, n_gates*td)`` so each program's weight/gate working set is
``td``-sized. The blocking is exact, NOT a block-diagonal approximation:
the hidden-to-gate matmul still consumes the full-width t-1 state (the
step's local row table is gathered once per step; with D > 1 the per-tile
aggregation is computed once per (t, j) at ``d == 0`` into a cache scratch
and re-read by the other d blocks; single-block layouts compute it inline
with no cache scratch), only the gate columns and state writes are
blocked. EvolveGCN's matrix-GRU evolves each weight COLUMN independently
(columns are the GRU batch), so its per-(l, d-block) evolution is exact
as well, and the documented padded-rows-stay-zero invariant holds per
block. ``td=None`` (one block) reproduces the fully resident layout
bit-for-bit.

HBM-paged residency (``residency="hbm_paged"``)
-----------------------------------------------
D-axis blocking shrinks the *working window* but the full state store
still occupies VMEM scratch, capping ``n_global × hidden`` at VMEM size.
The ``hbm_paged`` residency policy makes the same move the FPGA lineage
makes with DDR/HBM-resident state and multi-buffered streaming: paged
stores stay in HBM for the whole stream — the state enters the kernel as
an operand with ``memory_space=pl.ANY``, aliased in-place onto an
output via ``input_output_aliases`` — and the engine stages exactly the
``(n_global, td)`` column window each program needs through explicit
``pltpu.make_async_copy`` DMA:

  * **stage-in** (per step, at each (l, d) window's first tile): the read
    view's window is DMA'd into a VMEM staging buffer; for ping-pong
    states the read PLANE of an HBM A/B plane pair is selected by t's
    parity, and the stage-in doubles as the copy-forward (untouched rows
    ride staging into the write plane);
  * **cell windows**: ``gather_tile``/``scatter_tile``/``state_block``
    resolve to the staging buffer — cell bodies are residency-agnostic;
  * **ring-buffered full-width reads**: states declared ``full_read``
    (the t-1 store feeding aggregations/gates) sweep ALL D windows
    through a ``depth``-deep ring of staging buffers at each step's first
    program — ``_Engine.paged_fill`` starts window w+depth's copy before
    consuming window w (depth 2 = double-buffered, 4 = quad) — and the
    per-window row gather fills the same local-table columns the resident
    path fills, so the float math is bit-identical;
  * **write-back** (at the window's last tile, after the cell and the
    live-gated evolve hook): the dirty staging window is DMA'd to the
    write view (ping-pong: the opposite plane; row/weights: in place).

Only the read ring is depth-buffered; stage-in and write-back are
synchronous (start+wait) — the write must land before the next (d)
window reuses the staging buffer. Per paged state the scratch cost is
``(1 [+ depth if full_read]) × (n_global, td)`` staging plus DMA
semaphores — independent of ``d_pad`` — instead of the full store, which
is the unlock for stores larger than VMEM (``stream_call`` enforces the
``VMEM_BUDGET_BYTES`` scratch budget). Requires ``td`` blocking;
undefined for the "static" temporal contract (zero StateDefs — nothing
to page). ``hbm_paged`` ≡ ``vmem`` bit-for-bit is pinned per family by
tests/test_paged.py, solo + batched + ragged.

Batch axis: a LEADING GRID DIMENSION, not ``jax.vmap`` — the vmap batching
rule prepends its axis to the grid while forwarding ``compiler_params``
unchanged, so the declared ``dimension_semantics`` would no longer cover
the axes the ping-pong parity argument depends on. See
docs/stream_engine.md for the full grid contract, the per-family scratch
residency table, and the drain/live-gating semantics.

Correctness contract: identical math to the per-step V2 path + the models'
gather/scatter, verified against kernels/ref.py stream oracles and the
differential harness (v3 ≡ baseline ≡ batched-v3 row-sliced, blocked ≡
unblocked).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graph.padding import round_up as _round_up


def _ell_matrix(idx, coef, n_src: int):
    """Dense ``(tn, n_src)`` aggregation matrix of one ELL node tile:
    ``A[r, c] = sum_k coef[r, k] * (idx[r, k] == c)``. ``A @ table`` is the
    tile's ELL aggregation over a step-local ``(n_src, width)`` table;
    coef-0 padding lanes contribute nothing whatever id they carry. Built
    from iota compares over static lane slices — the in-kernel gather form
    the TPU compiler lowers."""
    tn, k = idx.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (tn, n_src), 1)
    a = jnp.zeros((tn, n_src), coef.dtype)
    for kk in range(k):
        a = a + jnp.where(idx[:, kk:kk + 1] == cols, coef[:, kk:kk + 1], 0.0)
    return a


def _lane(x, kk):
    """Lane ``kk`` (traced) of a ``(rows, k)`` value as a ``(rows, 1)``
    column: a masked lane sum, exact since every other lane adds zero."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lanes == kk, x, 0), axis=1, keepdims=True)


def _dot_exact(a, b):
    """``a @ b`` at full f32 precision — every matmul of the engine. The
    model is f32 end to end, and the aggregation matmul stands in for a
    gather, so no operand may round through bf16 (the compiler's default
    for f32 operands on the chip need not be full precision)."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _pad_dim(a, n2: int, axis: int, fill=0):
    """Pad ``a`` to ``n2`` entries along ``axis`` with a constant fill
    (shared with kernels/ops.py — the single copy of this helper)."""
    n = a.shape[axis]
    if n == n2:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, n2 - n)
    return jnp.pad(a, widths, constant_values=fill)


def _pack_gate_blocks(w, n_gates: int, td: int):
    """Re-pack a gate-concatenated weight ``(rows, n_gates*h)`` into
    per-d-block gate tiles ``(D, rows, n_gates*td)``.

    Block d holds columns [d*td, (d+1)*td) of EVERY gate, concatenated in
    gate order, so the kernel splits its gate tensor at ``td`` boundaries
    — the per-block edition of the fused-gate layout. Gate columns are
    zero-padded to D*td; padded gate columns produce zero pre-activations,
    which is what keeps the padded state columns at zero (see the cell
    bodies)."""
    rows = w.shape[0]
    gs = jnp.split(w, n_gates, axis=-1)
    d_pad = _round_up(gs[0].shape[-1], td)
    gs = [_pad_dim(g, d_pad, -1).reshape(rows, d_pad // td, td) for g in gs]
    packed = jnp.concatenate(gs, axis=-1)        # (rows, D, n_gates*td)
    return jnp.moveaxis(packed, 1, 0)            # (D, rows, n_gates*td)


def _pack_gate_bias(b, n_gates: int, td: int):
    """(n_gates*h,) -> (D, 1, n_gates*td) per-block gate bias (a row
    vector per block, so its VMEM block meets the tiling rule)."""
    return _pack_gate_blocks(b[None], n_gates, td)


# ------------------------------------------------------------------------
# Registry data model: a family is a declarative cell spec.

@dataclass(frozen=True)
class StateDef:
    """One recurrent state tensor of a family.

    kind:
      "pingpong"  neighbour-aggregated node state: within a step every
                  tile must see the t-1 store while tiles write the t
                  store, so the engine keeps an A/B plane pair swapped by
                  t's parity (scratch ``(2, n_global, d_pad)``).
      "row"       own-row node state (each row read/written by exactly
                  one tile per step): a single ``(n_global, d_pad)``
                  buffer suffices.
      "weights"   per-layer evolving weight matrices ``(L, d_pad, d_pad)``
                  (EvolveGCN), drained per (l, d-block).

    full_read: the cell body consumes the FULL-width t-1 view of this
    state (aggregations / hidden-to-gate matmuls), not just the current
    (d) window. Under ``hbm_paged`` residency such states sweep all D
    windows through the depth-buffered DMA ring (``_Engine.paged_fill``)
    into the family's local row table.
    """

    name: str
    kind: str
    full_read: bool = False


#: the temporal contracts a family may declare (CellSpec.temporal):
#:   "dense"   dense snapshot stream — T sequences a per-step recurrence
#:             (ragged streams masked in-launch via ``lengths``);
#:   "event"   ragged event stream — T sequences event BATCHES, per-event
#:             timestamps drive the time encoding, state updates touch
#:             only the event endpoints (``lengths`` generalizes from
#:             ragged-T to ragged per-event batches);
#:   "static"  no recurrence at all — T must be 1, the engine's state
#:             init/drain and evolve hooks are vacuous (zero StateDefs),
#:             and independent snapshots fold onto the B axis (the serve
#:             engine's express lane).
TEMPORAL_MODES = ("dense", "event", "static")

#: state-residency policies (the plan's ``state_residency`` field):
#:   "vmem"       resident: the full store lives in VMEM scratch across
#:                the T axis (the original layout);
#:   "hbm_paged"  paged: the store stays in HBM (ANY-memory-space operand
#:                aliased in-place) and the engine DMA-stages the
#:                ``(n_global, td)`` column windows through a small ring
#:                of VMEM staging buffers (see the module docstring).
RESIDENCY_MODES = ("vmem", "hbm_paged")

#: legal DMA staging-ring depths under ``hbm_paged`` (the plan's
#: ``buffer_depth``): 1 = synchronous per-window copies (the no-overlap
#: baseline the benchmark sweep measures against), 2 = double-buffered
#: (window d+1 copies in while window d computes), 4 = quad-buffered.
BUFFER_DEPTHS = (1, 2, 4)

#: Scoped-VMEM limit every launch asks the compiler for
#: (``vmem_limit_bytes``), and the scratch budget enforced at launch
#: assembly: a resident layout whose scratch exceeds it must page
#: (``residency="hbm_paged"``). The compiler's default scoped limit on
#: TPU v5e is 16 MiB, which a resident GCRN-M2 launch at BC-Alpha size
#: (3,468-row stores, double-buffered state blocks) exceeds from B=5
#: streams up; 64 MiB is half of a v5e's 128 MiB of VMEM. Module-level
#: so tests can tighten it to exercise the oversized-store path at
#: CI-friendly sizes.
VMEM_BUDGET_BYTES = 64 * 1024 * 1024


# ------------------------------------------------------------------------
# Ping-pong plane parity, as pure functions. The VMEM plane pair of a
# resident pingpong state, the HBM plane pair of a paged one and the
# host-side final-plane select must agree on one parity scheme; keeping
# all derivations here (and nowhere else) makes the parity a checkable
# contract — repro.analysis simulates a T-step stream through these
# helpers and cross-checks read-after-write consistency, and the engine's
# read/write views call them directly.

def paged_read_plane(t):
    """Plane of a pingpong pair holding the t-1 state at step t (the
    step's READ view). Plane 0 holds the initial state (init / builds
    stack ``[state0, zeros]``), so step 0 reads plane 0."""
    return t % 2


def paged_write_plane(t):
    """Plane step t's updates land in (the step's WRITE view) — always
    the opposite plane of ``paged_read_plane(t)``."""
    return 1 - (t % 2)


def paged_final_plane(t_steps: int) -> int:
    """Plane holding the final state after a ``t_steps``-long stream:
    whatever plane the last step wrote. ``stream_call`` slices this plane
    out of the returned (B, 2, G, d_pad) pair host-side."""
    return paged_write_plane(t_steps - 1)


# ------------------------------------------------------------------------
# Trace recorder hooks. ``repro.analysis`` verifies the paged DMA protocol
# (start/wait pairing, ring-slot reuse ordering, alias coverage) WITHOUT
# device execution: it installs a recorder and abstractly evaluates a
# launch (``jax.eval_shape``), so the kernel body's Python-level protocol
# runs at trace time while every DMA start/wait is logged. Production
# launches pay nothing: with no recorder installed ``_async_copy`` returns
# the raw ``pltpu.make_async_copy`` object.

_TRACE_RECORDER = None


def set_trace_recorder(rec):
    """Install a trace recorder (``None`` clears). The recorder sees
    ``rec.launch(family, launch)`` per assembled launch and
    ``rec.dma(event, op=..., state=..., window=..., slot=...)`` per DMA
    start/wait issued by the paged engine. Returns the previous recorder
    so callers can restore it. NOTE: recording happens at kernel TRACE
    time — clear ``stream_call``'s jit cache around a recorded sweep or a
    cached trace will replay silently with no events."""
    global _TRACE_RECORDER
    prev = _TRACE_RECORDER
    _TRACE_RECORDER = rec
    return prev


class _TracedCopy:
    """A ``make_async_copy`` wrapper that logs start/wait to the recorder
    before issuing the real DMA op (trace-time passthrough)."""

    def __init__(self, cp, rec, tag):
        self._cp = cp
        self._rec = rec
        self._tag = tag

    def start(self):
        self._rec.dma("start", **self._tag)
        self._cp.start()

    def wait(self):
        self._rec.dma("wait", **self._tag)
        self._cp.wait()


def _async_copy(src, dst, sem, *, op, state, window=None, slot=None):
    """The engine's single DMA constructor: ``pltpu.make_async_copy``
    plus the (no-op by default) trace hook. ``op`` names the protocol
    site ("stage_in" / "write_back" / "ring"), ``state`` the StateDef
    index, ``window``/``slot`` the ring position for ring copies."""
    cp = pltpu.make_async_copy(src, dst, sem)
    if _TRACE_RECORDER is None:
        return cp
    return _TracedCopy(cp, _TRACE_RECORDER,
                       dict(op=op, state=state, window=window, slot=slot))


@dataclass(frozen=True)
class CellSpec:
    """A DGNN family expressed against the stream engine.

    ``build(*arrays, tn, td)`` assembles the launch (inputs, block specs,
    scratch, meta) and binds the family's ``cell`` (per-program body) and
    optional ``evolve`` (between-snapshot hook, live-gated by the engine).

    ``temporal`` declares the family's time semantics (one of
    ``TEMPORAL_MODES``) — the engine derives its per-mode behavior from
    this declaration instead of assuming a dense snapshot stream: a
    "static" family must carry zero StateDefs and no evolve hook (checked
    at registration and again at launch), an "event" family's T axis
    counts event batches, and only "dense"/"event" families own recurrent
    state the serve engine must checkpoint.
    """

    name: str
    resident: str                 # what stays on-chip across T (for docs)
    states: tuple[StateDef, ...]
    build: Callable
    temporal: str = "dense"


@dataclass(frozen=True)
class _StateMeta:
    kind: str
    in_idx: int     # position of the state's initial value in the inputs
    out_idx: int    # position of the drained final state in the outputs
    scr_idx: int    # resident: the store scratch (pingpong: its (2, G,
                    # d_pad) plane pair); paged: the (G, td) staging slot
    ring_idx: int = -1   # paged full_read states: (depth, G, td) DMA ring
    sem_idx: int = -1    # paged states: DMA semaphore array (depth+1,) —
                         # slots [0, depth) ring, slot depth stage-in/
                         # write-back


@dataclass(frozen=True)
class _Meta:
    n_in: int
    n_out: int
    states: tuple[_StateMeta, ...]
    live_idx: Optional[int]       # input index of the (B, T) SMEM live flag
    td: int
    tn: int
    n_dblocks: int                # D = d_pad // td
    temporal: str = "dense"       # must equal the CellSpec's declaration
    paged: bool = False           # hbm_paged residency selected
    depth: int = 1                # DMA staging-ring depth (paged only)
    g_rows: int = 0               # state-store rows G (node families)
    rows_idx: Optional[int] = None  # input index of the (B, T, 1, n_pad)
                                    # SMEM global-row-id table
    stage_idx: Optional[int] = None  # scratch index of the (tn, td)
                                     # row-staging tile


@dataclass
class _Launch:
    grid: tuple
    inputs: tuple
    in_specs: list
    out_specs: list
    out_shape: list
    scratch: list
    meta: _Meta
    cell: Callable
    evolve: Optional[Callable]
    aliases: dict = field(default_factory=dict)  # input→output aliasing
                                                 # (paged in-place stores)


class _Engine:
    """Per-program view of the engine grid handed to cell/evolve hooks."""

    def __init__(self, meta: _Meta, ins=None, outs=None, scr=None):
        self.meta = meta
        self.td = meta.td
        self.paged = meta.paged
        self.g_rows = meta.g_rows
        self._ins = ins
        self._outs = outs
        self._scr = scr
        self.b = pl.program_id(0)
        self.t = pl.program_id(1)
        self.l = pl.program_id(2)
        self.d = pl.program_id(3)
        self.j = pl.program_id(4)
        self.n_layers = pl.num_programs(2)
        self.n_dblocks = meta.n_dblocks
        self.n_tiles = pl.num_programs(4)
        self.blk = self.window(self.d)
        # this program's node-tile rows of step-local (n_pad, ...) tables
        self.rows = pl.ds(pl.multiple_of(self.j * meta.tn, meta.tn), meta.tn)
        # each stream loads its state at its own first program (full width:
        # later d blocks read the full t-1 store through the caches)
        self.stream_start = jnp.logical_and(
            self.t == 0, jnp.logical_and(self.d == 0, self.j == 0))
        self.first_dblock = self.d == 0
        # first program of each (t, l): the step-local row tables fill here
        self.step_start = jnp.logical_and(self.d == 0, self.j == 0)
        self.last_tile = self.j == self.n_tiles - 1
        # last (t, j) program of the CURRENT stream — drain point for the
        # (l, d) window's state block
        self.stream_done = jnp.logical_and(
            self.t == pl.num_programs(1) - 1, self.last_tile)

    def window(self, w):
        """Column window ``w`` of a d_pad-wide array: the whole width when
        there is one block (static, so row copies lower on the chip)."""
        if self.meta.n_dblocks == 1:
            return slice(None)
        start = w * self.td
        if not isinstance(w, int):
            start = pl.multiple_of(start, self.td)
        return pl.ds(start, self.td)

    # ---------------------------------------------------- row traffic ----

    def row_id(self, r):
        """Global store row of this step's local node ``r`` (``g_rows`` is
        the drop sentinel of padding rows), read from the SMEM table."""
        return self._ins[self.meta.rows_idx][0, 0, 0, r]

    def _copy_rows(self, n: int, rid, src, dst):
        """``dst[r] = src[rid(r)]`` for ``r < n``, one full-width row per
        iteration; sentinel rows read as zero."""
        g_rows = self.g_rows

        def body(r, carry):
            g = rid(r)
            row = src[pl.ds(jnp.minimum(g, g_rows - 1), 1), :]
            dst[pl.ds(r, 1), :] = jnp.where(g < g_rows, row, 0.0)
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    def _store(self, i: int, plane):
        """Ref view of resident state i's ``(G, d_pad)`` store (pingpong:
        the given plane of its pair)."""
        sm = self.meta.states[i]
        ref = self._scr[sm.scr_idx]
        return ref.at[plane] if sm.kind == "pingpong" else ref

    def _window_view(self, i: int, write: bool):
        """State i's current (d) column window: the t-1 view, or the step's
        write view. Paged: the staging buffer (stage-in'd from the HBM read
        view at the window's first tile, written back at its last)."""
        if self.paged:
            return self._scr[self.meta.states[i].scr_idx]
        plane = (paged_write_plane(self.t) if write
                 else paged_read_plane(self.t))
        return self._store(i, plane).at[:, self.blk]

    def gather_step(self, i: int, dst):
        """``dst`` (n_pad, d_pad) <- the full-width t-1 row of state i of
        every local node of this step (zero on padding rows). Call at
        ``step_start``: later programs of the step read the table while
        tiles scatter the step's updates. Paged: the t-1 windows sweep
        through the DMA ring and fill the table window by window."""
        n = dst.shape[0]
        if self.paged:
            self.paged_fill(i, lambda w, wblk, slot: self._copy_rows(
                n, self.row_id, slot, dst.at[:, wblk]))
        else:
            self._copy_rows(n, self.row_id,
                            self._store(i, paged_read_plane(self.t)), dst)

    def gather_tile(self, i: int):
        """This tile's own (tn, td) rows of state i's current window (t-1
        values: a tile reads its rows before scattering), copied through
        the row-staging tile."""
        stage = self._scr[self.meta.stage_idx]
        base = self.j * self.meta.tn
        self._copy_rows(stage.shape[0], lambda r: self.row_id(base + r),
                        self._window_view(i, write=False), stage)
        return stage[...]

    def scatter_tile(self, i: int, val):
        """State i's write window <- ``val`` (tn, td), one row per local
        node of this tile, copied through the row-staging tile; padding
        rows (the ``g_rows`` sentinel) drop. Pingpong states write the
        step's parity-selected plane; paged states scatter into the
        staging window (written back to the HBM write view at the
        window's last tile)."""
        src = self._scr[self.meta.stage_idx]
        src[...] = val
        dst = self._window_view(i, write=True)
        g_rows = self.g_rows
        base = self.j * self.meta.tn

        def body(r, carry):
            g = self.row_id(base + r)

            @pl.when(g < g_rows)
            def _store_row():
                dst[pl.ds(g, 1), :] = src[pl.ds(r, 1), :]

            return carry

        jax.lax.fori_loop(0, src.shape[0], body, 0)

    def state_block(self, scr, i: int):
        """Layer l's (d_pad, td) column block of a weights-kind state."""
        sm = self.meta.states[i]
        if self.paged:
            return scr[sm.scr_idx][...]
        return scr[sm.scr_idx][pl.ds(self.l, 1), :, self.blk][0]

    def state_block_store(self, scr, i: int, val):
        """Store layer l's evolved (d_pad, td) column block."""
        sm = self.meta.states[i]
        if self.paged:
            scr[sm.scr_idx][...] = val
        else:
            scr[sm.scr_idx][pl.ds(self.l, 1), :, self.blk] = val[None]

    # --------------------------------------------- paged DMA protocol ----
    # The HBM-resident view of paged state i is its ALIASED OUTPUT ref
    # (memory_space=ANY): reads and writes both go through it, so the
    # store evolves in place across the stream. Plane layouts: pingpong
    # (B, 2, G, d_pad) — plane t%2 is step t's read view, 1-(t%2) its
    # write view (the A/B parity argument verbatim, lifted to HBM); row
    # (B, 1, G, d_pad); weights (B, L, d_pad, d_pad).

    def _hbm(self, i: int):
        return self._outs[self.meta.states[i].out_idx]

    def _read_view(self, i: int, wblk):
        """HBM read view of state i's column window ``wblk`` (t-1)."""
        sm = self.meta.states[i]
        hbm = self._hbm(i)
        if sm.kind == "pingpong":
            return hbm.at[self.b, paged_read_plane(self.t), :, wblk]
        if sm.kind == "row":
            return hbm.at[self.b, 0, :, wblk]
        return hbm.at[self.b, self.l, :, wblk]

    def _write_view(self, i: int):
        """HBM write view of state i's CURRENT (d) window (step t)."""
        sm = self.meta.states[i]
        hbm = self._hbm(i)
        if sm.kind == "pingpong":
            return hbm.at[self.b, paged_write_plane(self.t), :, self.blk]
        if sm.kind == "row":
            return hbm.at[self.b, 0, :, self.blk]
        return hbm.at[self.b, self.l, :, self.blk]

    def stage_in(self, i: int):
        """Synchronous DMA of the current (d) window's t-1 values into
        the staging buffer (the window's first tile). For pingpong states
        this doubles as the copy-forward: rows the step does not scatter
        ride staging into the write plane at write-back."""
        sm = self.meta.states[i]
        sem = self._scr[sm.sem_idx].at[self.meta.depth]
        cp = _async_copy(self._read_view(i, self.blk),
                         self._scr[sm.scr_idx], sem,
                         op="stage_in", state=i)
        cp.start()
        cp.wait()

    def write_back(self, i: int):
        """Synchronous DMA of the dirty staging window to the HBM write
        view (the window's last tile, after cell + evolve). Synchronous
        on purpose: the next (d) window reuses the staging buffer."""
        sm = self.meta.states[i]
        sem = self._scr[sm.sem_idx].at[self.meta.depth]
        cp = _async_copy(self._scr[sm.scr_idx],
                         self._write_view(i), sem,
                         op="write_back", state=i)
        cp.start()
        cp.wait()

    def paged_fill(self, i: int, fill):
        """Ring-buffered sweep over ALL D column windows of paged state
        i's t-1 (read) view: ``fill(w, wblk, slot)`` runs per window w
        with ``slot`` the (G, td) ring buffer holding it, while window
        w+depth's DMA is already in flight (depth 2 = double-, 4 =
        quad-buffered; depth 1 degenerates to synchronous per-window
        copies). The per-window fills write disjoint table columns, so
        the float math matches the resident full-width fill bit-for-bit."""
        sm = self.meta.states[i]
        ring = self._scr[sm.ring_idx]
        sems = self._scr[sm.sem_idx]
        depth = self.meta.depth
        n_win = self.n_dblocks
        dmas = {}

        def _start(w):
            slot = w % depth
            dma = _async_copy(
                self._read_view(i, self.window(w)),
                ring.at[slot], sems.at[slot],
                op="ring", state=i, window=w, slot=slot)
            dma.start()
            dmas[w] = dma

        for w in range(min(depth, n_win)):
            _start(w)
        for w in range(n_win):
            dmas.pop(w).wait()
            fill(w, self.window(w), ring.at[w % depth])
            if w + depth < n_win:
                _start(w + depth)


# ------------------------------------------------------------------------
# THE stream-engine kernel body. The only Pallas kernel in this module:
# every family runs through it; family code enters via cell/evolve hooks.

def _stream_engine_kernel(cell, evolve, meta: _Meta, *refs):
    ins = refs[:meta.n_in]
    outs = refs[meta.n_in:meta.n_in + meta.n_out]
    scr = refs[meta.n_in + meta.n_out:]
    eng = _Engine(meta, ins, outs, scr)

    if meta.paged:
        # --- paged stage-in (engine-owned): the state lives in HBM (the
        # aliased ANY-space output ref), so there is no stream init and no
        # resident copy-forward — at each (l, d) window's first tile the
        # t-1 window is DMA'd into VMEM staging. For pingpong states the
        # stage-in from the read plane IS the copy-forward (write-back
        # pushes untouched rows into the write plane with the rest).
        for i in range(len(meta.states)):

            @pl.when(eng.j == 0)
            def _stage(i=i):
                eng.stage_in(i)
    else:
        # --- stream-boundary init (engine-owned): every stream
        # re-initializes the scratch from its OWN state block at its first
        # program, so streams reuse the buffers serially and each restarts
        # the ping-pong at plane 0. Weight states init per layer (each l
        # has its own first program on the (d==0, j==0) plane).
        for sm in meta.states:
            in_ref = ins[sm.in_idx]

            @pl.when(eng.stream_start)
            def _init(sm=sm, in_ref=in_ref):
                if sm.kind == "pingpong":
                    scr[sm.scr_idx][0] = in_ref[0]
                elif sm.kind == "row":
                    scr[sm.scr_idx][...] = in_ref[0]
                else:  # weights: full (d_pad, d_pad) block of layer l
                    scr[sm.scr_idx][pl.ds(eng.l, 1)] = in_ref[0]

        # --- ping-pong copy-forward (engine-owned): at the start of each
        # step copy the read window into the write window so rows this
        # snapshot does not touch carry over; tiles then overwrite only
        # their own rows.
        for sm in meta.states:
            if sm.kind != "pingpong":
                continue
            st = scr[sm.scr_idx]

            @pl.when(eng.j == 0)
            def _fwd(st=st):
                st[paged_write_plane(eng.t), :, eng.blk] = (
                    st[paged_read_plane(eng.t), :, eng.blk])

    # --- the family's per-(t, l, d, j) cell body
    cell(eng, ins, outs, scr)

    # --- between-snapshot evolution (weights-evolved families), gated by
    # the live flag: no-op (all-padding) snapshots are not steps of the
    # stream and must never advance the recurrence.
    if evolve is not None:
        live = ins[meta.live_idx][eng.b, eng.t] > 0

        @pl.when(jnp.logical_and(eng.last_tile, live))
        def _evolve():
            evolve(eng, ins, scr)

    if meta.paged:
        # --- paged write-back (engine-owned): every (l, d) window's last
        # tile DMAs the dirty staging window to the HBM write view (after
        # the cell and the live-gated evolve hook). There is no separate
        # drain — the store evolves in place; ``stream_call`` selects the
        # final plane of pingpong pairs host-side from T's parity.
        for i in range(len(meta.states)):

            @pl.when(eng.last_tile)
            def _wb(i=i):
                eng.write_back(i)
    else:
        # --- drain (engine-owned): this stream's last program of each
        # (l, d) window writes the final state block (AFTER the final
        # live step's update/evolution) back to HBM.
        for sm in meta.states:
            out_ref = outs[sm.out_idx]

            @pl.when(eng.stream_done)
            def _drain(sm=sm, out_ref=out_ref):
                if sm.kind == "pingpong":
                    out_ref[0] = scr[sm.scr_idx][paged_write_plane(eng.t),
                                                 :, eng.blk]
                elif sm.kind == "row":
                    out_ref[0] = scr[sm.scr_idx][:, eng.blk]
                else:
                    out_ref[0, 0] = scr[sm.scr_idx][pl.ds(eng.l, 1), :,
                                                    eng.blk][0]


def launch_scratch_bytes(launch: _Launch) -> int:
    """Total VMEM scratch bytes of an assembled launch (semaphore scratch
    lives in semaphore memory and is excluded). The ground truth the
    plan-time estimator ``stream_vmem_bytes`` is tested against."""
    total = 0
    for s in launch.scratch:
        if getattr(s, "memory_space", None) != pltpu.VMEM:
            continue
        total += int(jnp.dtype(s.dtype).itemsize) * int(
            functools.reduce(lambda a, b: a * b, s.shape, 1))
    return total


def stream_vmem_bytes(family: str, *, g_rows: int = 0, n_pad: int = 0,
                      d_pad: int = 0, din: int = 0, dmid: int = 0,
                      n_layers: int = 1, td: Optional[int] = None,
                      tn: int = 128, residency: str = "vmem",
                      depth: int = 2, itemsize: int = 4) -> int:
    """Plan-time VMEM scratch estimate per family/residency/blocking —
    the per-family scratch tables (docs/stream_engine.md) as a formula.
    Bit-equal to ``launch_scratch_bytes`` of the assembled launch
    (tests/test_paged.py pins this for every family and variant).

    ``g_rows`` counts the state-store rows of node families; ``n_pad``
    the padded per-step node count; ``din``/``dmid`` the gcrn
    aggregation-input / stacked GCN-mid widths; ``tn`` the node tile.
    Node families always hold one step-local ``(n_pad, d_pad)`` row table
    of the t-1 store and one ``(tn, td)`` row-staging tile."""
    paged = residency == "hbm_paged"
    if paged and family == "static_gcn":
        raise ValueError("static_gcn has no state to page")
    t = td if td is not None else d_pad
    n_win = -(-d_pad // t) if t else 1  # ceil
    cached = n_win > 1 or paged
    table = n_pad * d_pad + tn * t        # row table + row-staging tile
    cells = 0
    if family == "gcrn":
        store = (2 + depth) * g_rows * t if paged else 3 * g_rows * d_pad
        cells = store + table + (n_pad * (din + d_pad) if cached else 0)
    elif family == "stacked":
        store = (1 + depth) * g_rows * t if paged else g_rows * d_pad
        cells = store + table + (n_pad * dmid if cached else 0)
    elif family == "evolve":
        if paged:
            cells = d_pad * t + 3 * n_pad * d_pad
        else:
            cells = (n_layers * d_pad * d_pad + 2 * n_pad * d_pad
                     + (n_pad * d_pad if cached else 0))
    elif family == "tgn":
        store = (1 + depth) * g_rows * t if paged else 2 * g_rows * d_pad
        cells = store + table + (n_pad * d_pad if cached else 0)
    elif family == "static_gcn":
        cells = 2 * n_pad * d_pad + (n_pad * d_pad if cached else 0)
    else:
        raise KeyError(family)
    return cells * itemsize


@functools.partial(jax.jit,
                   static_argnames=("family", "tn", "td", "interpret",
                                    "residency", "depth"))
def stream_call(family: str, *args, tn: int = 128, td: Optional[int] = None,
                interpret: bool = False, residency: str = "vmem",
                depth: int = 2):
    """Run a (B, T, ...) snapshot-stream batch through the stream engine.

    The single registry dispatch point: ``family`` selects a cell spec
    whose ``build`` assembles the launch; the engine kernel body is shared.
    ``td`` blocks the state feature axis (None = one block, fully
    resident); ``residency`` selects where the state store lives across
    the stream ("vmem" resident scratch / "hbm_paged" DMA-staged windows,
    ``depth``-deep read ring — see the module docstring). Callers go
    through kernels/ops.py, which owns padding, oracle routing, and
    output slicing.
    """
    spec = REGISTRY[family]
    if residency not in RESIDENCY_MODES:
        raise ValueError(
            f"unknown state residency {residency!r}; expected one of "
            f"{RESIDENCY_MODES}")
    paged = residency == "hbm_paged"
    if paged:
        if spec.temporal == "static":
            raise ValueError(
                f"state_residency='hbm_paged' is undefined for static "
                f"family {family!r}: zero StateDefs — there is no "
                "recurrent store to page")
        if td is None:
            raise ValueError(
                "state_residency='hbm_paged' requires td blocking: td "
                "is the (n_global, td) paging window the DMA ring "
                "stages (td=None keeps the store fully VMEM-resident)")
        if depth not in BUFFER_DEPTHS:
            raise ValueError(
                f"buffer_depth must be one of {BUFFER_DEPTHS}, "
                f"got {depth}")
    launch = spec.build(*args, tn=tn, td=td, residency=residency,
                        depth=depth)
    if launch.meta.temporal != spec.temporal:
        raise ValueError(
            f"family {family!r} built a launch declaring temporal="
            f"{launch.meta.temporal!r} but its cell spec declares "
            f"{spec.temporal!r}")
    if spec.temporal == "static" and (launch.meta.states
                                      or launch.evolve is not None):
        raise ValueError(
            f"static family {family!r} must launch with zero state "
            "tensors and no evolve hook")
    scratch_bytes = launch_scratch_bytes(launch)
    if scratch_bytes > VMEM_BUDGET_BYTES:
        hint = ("shrink td" if paged else
                "page the state store with plan(state_residency="
                "'hbm_paged', td=...)")
        raise ValueError(
            f"family {family!r} ({residency}, td={td}) needs "
            f"{scratch_bytes} bytes of VMEM scratch, over the "
            f"{VMEM_BUDGET_BYTES}-byte budget — {hint}")
    if _TRACE_RECORDER is not None:
        _TRACE_RECORDER.launch(family, launch)
    kernel = functools.partial(_stream_engine_kernel, launch.cell,
                               launch.evolve, launch.meta)
    res = pl.pallas_call(
        kernel,
        grid=launch.grid,
        in_specs=launch.in_specs,
        out_specs=launch.out_specs,
        out_shape=launch.out_shape,
        scratch_shapes=launch.scratch,
        input_output_aliases=launch.aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(launch.grid),
            vmem_limit_bytes=VMEM_BUDGET_BYTES),
        interpret=interpret,
        name=f"stream_engine_{family}",
    )(*launch.inputs)
    if paged:
        # node-state planes come back as (B, P, G, d_pad): select the
        # plane the last step wrote (static in T) so callers see the
        # resident output shapes; weights evolved in place, no planes.
        res = list(res)
        t_steps = launch.grid[1]
        for sm in launch.meta.states:
            if sm.kind == "pingpong":
                res[sm.out_idx] = res[sm.out_idx][:, paged_final_plane(t_steps)]
            elif sm.kind == "row":
                res[sm.out_idx] = res[sm.out_idx][:, 0]
    return res


# ------------------------------------------------------------------------
# Block specs shared by the families. Every per-step node table is
# (B, T, n_pad, width); per-node vectors ride as (tn, 1) column blocks and
# the global-row-id table as a (1, n_pad) SMEM block per step.

def _tile_spec(tn: int, width: int):
    """One node tile of a per-step (B, T, n_pad, width) array."""
    return pl.BlockSpec((1, 1, tn, width), lambda bi, t, l, d, j: (bi, t, j, 0))


def _step_spec(n: int, width: int):
    """The whole (n_pad, width) table of a step (e.g. node features)."""
    return pl.BlockSpec((1, 1, n, width), lambda bi, t, l, d, j: (bi, t, 0, 0))


def _row_ids_spec(n: int):
    """The step's (1, n_pad) global-row-id table, in SMEM."""
    return pl.BlockSpec((1, 1, 1, n), lambda bi, t, l, d, j: (bi, t, 0, 0),
                        memory_space=pltpu.SMEM)


def _out_tile_spec(tn: int, td: int):
    """Per-step output tile (node tile j, state column block d)."""
    return pl.BlockSpec((1, 1, tn, td), lambda bi, t, l, d, j: (bi, t, j, d))


def _gate_specs(rows: int, n_gates: int, td: int, wx_rows: int):
    """Per-d-block gate tiles: wx (D, wx_rows, g*td), wh (D, rows, g*td),
    bias (D, 1, g*td)."""
    dblk = lambda bi, t, l, d, j: (d, 0, 0)
    return [pl.BlockSpec((1, wx_rows, n_gates * td), dblk),
            pl.BlockSpec((1, rows, n_gates * td), dblk),
            pl.BlockSpec((1, 1, n_gates * td), dblk)]


def _edge_agg_spec(edge_agg, tn: int, width: int, dtype):
    """(array, spec) of a pre-aggregated (B, T, n_pad, width) edge term;
    without one, a single pinned zero block the kernel never reads."""
    if edge_agg is not None:
        return edge_agg, _tile_spec(tn, width)
    return (jnp.zeros((1, 1, tn, width), dtype),
            pl.BlockSpec((1, 1, tn, width),
                         lambda bi, t, l, d, j: (0, 0, 0, 0)))


def _node_state_io(h0, d_pad: int, td: int, pingpong: bool, paged: bool):
    """(input, in_spec, out_spec, out_shape) of one (B, G, h) node-state
    store, zero-padded to d_pad columns. Resident: the store in, drained
    per d window out. Paged: an HBM plane stack (pingpong: ``[state0,
    zeros]`` A/B planes; row: one plane) aliased in place onto its
    output."""
    h0 = _pad_dim(h0, d_pad, -1)
    B, G = h0.shape[0], h0.shape[1]
    if paged:
        planes = 2 if pingpong else 1
        h_in = (jnp.stack([h0, jnp.zeros_like(h0)], axis=1) if pingpong
                else h0[:, None])
        spec = pl.BlockSpec(memory_space=pl.ANY)
        return (h_in, spec, spec,
                jax.ShapeDtypeStruct((B, planes, G, d_pad), h0.dtype))
    return (h0,
            pl.BlockSpec((1, G, d_pad), lambda bi, t, l, d, j: (bi, 0, 0)),
            pl.BlockSpec((1, G, td), lambda bi, t, l, d, j: (bi, 0, d)),
            jax.ShapeDtypeStruct((B, G, d_pad), h0.dtype))


# ------------------------------------------------------------------------
# GCRN (GC-LSTM): integrated family. Neighbour-aggregated h (ping-pong
# pair) + own-row c. The hidden-to-gate matmul consumes the FULL-width t-1
# store: the step's node rows are gathered once per step into a local
# table and aggregated per tile (cached at d == 0 when D > 1); gate
# columns and state writes are d-blocked.

_GCRN_HLOC, _GCRN_CAX, _GCRN_CAH = 2, 4, 5   # scratch slots (both residencies)


def _gcrn_cell(has_edge, cached, eng, ins, outs, scr):
    (idx_ref, coef_ref, x_ref, _rowg, mask_ref, _h0, _c0, wx_ref, wh_ref,
     b_ref, eagg_ref) = ins
    out_ref = outs[0]
    hloc = scr[_GCRN_HLOC]
    mask = mask_ref[0, 0]                       # (tn, 1)
    rows = eng.rows

    @pl.when(eng.step_start)
    def _gather():
        eng.gather_step(0, hloc)                # t-1 h of the step's nodes

    def _aggregates():
        a = _ell_matrix(idx_ref[0, 0], coef_ref[0, 0], x_ref.shape[2])
        agg_x = _dot_exact(a, x_ref[0, 0])
        if has_edge:
            agg_x = agg_x + eagg_ref[0, 0]
        return agg_x, _dot_exact(a, hloc[...])

    if cached:  # D > 1 or paged: aggregate once per (t, j); d > 0 re-reads
        cax, cah = scr[_GCRN_CAX], scr[_GCRN_CAH]

        @pl.when(eng.first_dblock)
        def _fill_caches():
            cax[rows], cah[rows] = _aggregates()

        agg_x, agg_h = cax[rows], cah[rows]
    else:       # single d block: inline, no scratch round-trip
        agg_x, agg_h = _aggregates()

    td = eng.td
    gates = (_dot_exact(agg_x, wx_ref[0]) + _dot_exact(agg_h, wh_ref[0])
             + b_ref[0])
    i = gates[:, :td]
    f = gates[:, td:2 * td]
    g = gates[:, 2 * td:3 * td]
    o = gates[:, 3 * td:]

    c_old = eng.gather_tile(1) * mask
    c_new = (jax.nn.sigmoid(f) * c_old + jax.nn.sigmoid(i) * jnp.tanh(g)) * mask
    h_new = (jax.nn.sigmoid(o) * jnp.tanh(c_new)) * mask
    eng.scatter_tile(0, h_new)
    eng.scatter_tile(1, c_new)
    out_ref[0, 0] = h_new


def _gcrn_build(neigh_idx, neigh_coef, node_feat, row_gidx, node_mask,
                h0, c0, wx, wh, b, edge_agg=None, *,
                tn: int, td: Optional[int], residency: str = "vmem",
                depth: int = 2):
    B, T, n, k = neigh_idx.shape
    din, h = node_feat.shape[3], h0.shape[2]
    G = h0.shape[1]
    assert n % tn == 0
    paged = residency == "hbm_paged"
    td = h if td is None else td
    d_pad = _round_up(h, td)
    D = d_pad // td
    cached = D > 1 or paged
    grid = (B, T, 1, D, n // tn)

    wxp = _pack_gate_blocks(wx, 4, td)                    # (D, din, 4td)
    whp = _pack_gate_blocks(_pad_dim(wh, d_pad, 0), 4, td)  # (D, d_pad, 4td)
    bp = _pack_gate_bias(b, 4, td)                        # (D, 1, 4td)
    has_edge = edge_agg is not None
    eagg, eagg_spec = _edge_agg_spec(edge_agg, tn, din, node_feat.dtype)
    h_in, h_in_spec, h_out_spec, h_out_shape = _node_state_io(
        h0, d_pad, td, True, paged)
    c_in, c_in_spec, c_out_spec, c_out_shape = _node_state_io(
        c0, d_pad, td, False, paged)

    tables = [
        pltpu.VMEM((n, d_pad), h0.dtype),             # t-1 h row table
        pltpu.VMEM((tn, td), h0.dtype),               # row-staging tile
    ] + ([
        pltpu.VMEM((n, din), node_feat.dtype),        # agg_x cache
        pltpu.VMEM((n, d_pad), h0.dtype),             # agg_h cache
    ] if cached else [])
    if paged:
        # HBM-resident stores: h as an A/B plane pair (stage-in reads the
        # t%2 plane, write-back the other), c as a single plane; both
        # aliased in-place onto their outputs.
        states = (_StateMeta("pingpong", in_idx=5, out_idx=1, scr_idx=0,
                             ring_idx=6, sem_idx=7),
                  _StateMeta("row", in_idx=6, out_idx=2, scr_idx=1,
                             sem_idx=8))
        scratch = [
            pltpu.VMEM((G, td), h0.dtype),            # h staging window
            pltpu.VMEM((G, td), c0.dtype),            # c staging window
        ] + tables + [
            pltpu.VMEM((depth, G, td), h0.dtype),     # h read ring
            pltpu.SemaphoreType.DMA((depth + 1,)),
            pltpu.SemaphoreType.DMA((depth + 1,)),
        ]
        aliases = {5: 1, 6: 2}
    else:
        states = (_StateMeta("pingpong", in_idx=5, out_idx=1, scr_idx=0),
                  _StateMeta("row", in_idx=6, out_idx=2, scr_idx=1))
        scratch = [
            pltpu.VMEM((2, G, d_pad), h0.dtype),      # h plane pair
            pltpu.VMEM((G, d_pad), c0.dtype),         # c (own-row)
        ] + tables
        aliases = {}

    meta = _Meta(
        n_in=11, n_out=3, states=states, live_idx=None, td=td, tn=tn,
        n_dblocks=D, paged=paged, depth=depth, g_rows=G, rows_idx=3,
        stage_idx=3)
    return _Launch(
        grid=grid,
        inputs=(neigh_idx, neigh_coef, node_feat, row_gidx[:, :, None, :],
                node_mask[..., None], h_in, c_in, wxp, whp, bp, eagg),
        in_specs=[
            _tile_spec(tn, k),                        # neigh_idx (local)
            _tile_spec(tn, k),                        # neigh_coef
            _step_spec(n, din),                       # node_feat, per (b, t)
            _row_ids_spec(n),                         # global row ids (SMEM)
            _tile_spec(tn, 1),                        # node_mask column
            h_in_spec,                                # h0 / h plane pair
            c_in_spec,                                # c0 / c plane
            *_gate_specs(d_pad, 4, td, din),          # wx / wh / bias tiles
            eagg_spec,                                # edge-message term
        ],
        out_specs=[
            _out_tile_spec(tn, td),                   # per-step h outputs
            h_out_spec,                               # final h
            c_out_spec,                               # final c
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, n, d_pad), node_feat.dtype),
            h_out_shape, c_out_shape,
        ],
        scratch=scratch,
        meta=meta,
        cell=functools.partial(_gcrn_cell, has_edge, cached),
        evolve=None,
        aliases=aliases,
    )


# ------------------------------------------------------------------------
# Stacked DGNN (GCN -> GRU): own-row h only. The GRU's hidden-to-gate
# matmul reads the FULL-width t-1 row: the step's rows are gathered into a
# local table at the step's first program, BEFORE any tile writes.

_STACKED_HLOC, _STACKED_CNT = 1, 3           # scratch slots (both residencies)


def _stacked_cell(has_edge, cached, eng, ins, outs, scr):
    (idx_ref, coef_ref, x_ref, _rowg, mask_ref, _h0, wg_ref, bg_ref,
     wx_ref, wh_ref, b_ref, eagg_ref) = ins
    out_ref = outs[0]
    hloc = scr[_STACKED_HLOC]
    mask = mask_ref[0, 0]
    rows = eng.rows

    @pl.when(eng.step_start)
    def _gather():
        eng.gather_step(0, hloc)                # t-1 own rows, pre-write

    def _node_transform():
        a = _ell_matrix(idx_ref[0, 0], coef_ref[0, 0], x_ref.shape[2])
        agg = _dot_exact(a, x_ref[0, 0])
        if has_edge:
            agg = agg + eagg_ref[0, 0]
        return _dot_exact(agg, wg_ref[...]) + bg_ref[...]

    if cached:  # D > 1 or paged: once per (t, j); d > 0 re-reads
        cnt = scr[_STACKED_CNT]

        @pl.when(eng.first_dblock)
        def _fill_cache():
            cnt[rows] = _node_transform()

        nt = cnt[rows]
    else:       # single d block: inline
        nt = _node_transform()

    td = eng.td
    gx = _dot_exact(nt, wx_ref[0]) + b_ref[0]
    gh = _dot_exact(hloc[rows] * mask, wh_ref[0])
    rx, zx, nx = gx[:, :td], gx[:, td:2 * td], gx[:, 2 * td:]
    rh, zh, nh = gh[:, :td], gh[:, td:2 * td], gh[:, 2 * td:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    nn = jnp.tanh(nx + r * nh)
    h_old = hloc[rows, eng.blk] * mask
    h_new = ((1.0 - z) * nn + z * h_old) * mask
    eng.scatter_tile(0, h_new)
    out_ref[0, 0] = h_new


def _stacked_build(neigh_idx, neigh_coef, node_feat, row_gidx, node_mask,
                   h0, w_gcn, b_gcn, wx, wh, b, edge_agg=None, *,
                   tn: int, td: Optional[int], residency: str = "vmem",
                   depth: int = 2):
    B, T, n, k = neigh_idx.shape
    din, h = node_feat.shape[3], h0.shape[2]
    dmid = w_gcn.shape[1]
    G = h0.shape[1]
    assert n % tn == 0
    paged = residency == "hbm_paged"
    td = h if td is None else td
    d_pad = _round_up(h, td)
    D = d_pad // td
    cached = D > 1 or paged
    grid = (B, T, 1, D, n // tn)

    wxp = _pack_gate_blocks(wx, 3, td)                      # (D, dmid, 3td)
    whp = _pack_gate_blocks(_pad_dim(wh, d_pad, 0), 3, td)  # (D, d_pad, 3td)
    bp = _pack_gate_bias(b, 3, td)                          # (D, 1, 3td)
    has_edge = edge_agg is not None
    eagg, eagg_spec = _edge_agg_spec(edge_agg, tn, din, node_feat.dtype)
    h_in, h_in_spec, h_out_spec, h_out_shape = _node_state_io(
        h0, d_pad, td, False, paged)

    tables = [
        pltpu.VMEM((n, d_pad), h0.dtype),              # t-1 h row table
        pltpu.VMEM((tn, td), h0.dtype),                # row-staging tile
    ] + ([
        pltpu.VMEM((n, dmid), node_feat.dtype),        # node-transform cache
    ] if cached else [])
    if paged:
        # HBM-resident own-row store as a single plane, aliased in-place
        # onto its output.
        states = (_StateMeta("row", in_idx=5, out_idx=1, scr_idx=0,
                             ring_idx=4, sem_idx=5),)
        scratch = [pltpu.VMEM((G, td), h0.dtype)] + tables + [  # staging
            pltpu.VMEM((depth, G, td), h0.dtype),               # read ring
            pltpu.SemaphoreType.DMA((depth + 1,)),
        ]
        aliases = {5: 1}
    else:
        states = (_StateMeta("row", in_idx=5, out_idx=1, scr_idx=0),)
        scratch = [pltpu.VMEM((G, d_pad), h0.dtype)] + tables   # h store
        aliases = {}

    res2 = lambda bi, t, l, d, j: (0, 0)
    meta = _Meta(
        n_in=12, n_out=2, states=states, live_idx=None, td=td, tn=tn,
        n_dblocks=D, paged=paged, depth=depth, g_rows=G, rows_idx=3,
        stage_idx=2)
    return _Launch(
        grid=grid,
        inputs=(neigh_idx, neigh_coef, node_feat, row_gidx[:, :, None, :],
                node_mask[..., None], h_in, w_gcn, b_gcn[None], wxp, whp, bp,
                eagg),
        in_specs=[
            _tile_spec(tn, k),                         # neigh_idx (local)
            _tile_spec(tn, k),                         # neigh_coef
            _step_spec(n, din),                        # node_feat
            _row_ids_spec(n),                          # global row ids (SMEM)
            _tile_spec(tn, 1),                         # node_mask column
            h_in_spec,                                 # h0 / h plane
            pl.BlockSpec((din, dmid), res2),           # GCN weight (full)
            pl.BlockSpec((1, dmid), res2),             # GCN bias
            *_gate_specs(d_pad, 3, td, dmid),          # wx / wh / bias tiles
            eagg_spec,                                 # edge-message term
        ],
        out_specs=[
            _out_tile_spec(tn, td),
            h_out_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, n, d_pad), node_feat.dtype),
            h_out_shape,
        ],
        scratch=scratch,
        meta=meta,
        cell=functools.partial(_stacked_cell, has_edge, cached),
        evolve=None,
        aliases=aliases,
    )


# ------------------------------------------------------------------------
# GCN layer over an activation plane pair: the per-(t, l, d, j) body of
# both layer-sequenced families (EvolveGCN, static GCN). The L grid axis
# sequences the multi-layer GCN's cross-tile dependency: even layers read
# plane 0 and write plane 1, odd layers the reverse; layer 0 reads this
# step's node features.

def _gcn_layer(has_edge, eng, idx_ref, coef_ref, x_ref, mask_ref, eagg_ref,
               xact, cagg, w_blk, bias, out_ref):
    l = eng.l
    rows = eng.rows

    @pl.when(jnp.logical_and(l == 0, eng.step_start))
    def _init_x():
        xact[0] = x_ref[0, 0]

    src = l % 2

    def _aggregate():
        a = _ell_matrix(idx_ref[0, 0], coef_ref[0, 0], xact.shape[1])
        out = _dot_exact(a, xact[src])
        return out + eagg_ref[0, 0, 0] if has_edge else out

    if cagg is not None:  # D > 1: aggregate once per (t, l, j); d > 0 re-reads
        @pl.when(eng.first_dblock)
        def _fill_cache():
            cagg[rows] = _aggregate()

        agg = cagg[rows]
    else:                 # single d block: inline, no scratch round-trip
        agg = _aggregate()

    h = _dot_exact(agg, w_blk) + bias
    h = jnp.where(l == eng.n_layers - 1, h, jnp.maximum(h, 0.0)) * mask_ref[0, 0]
    xact[1 - src, rows, eng.blk] = h

    # model output = last layer's (masked, linear) activations
    @pl.when(l == eng.n_layers - 1)
    def _out():
        out_ref[0, 0] = h


def _layer_specs(n: int, tn: int, k: int, d_pad: int, has_edge: bool):
    """(idx, coef, node_feat, mask) specs of the layer-sequenced families,
    plus the edge-term spec: per-layer (B, T, L, n_pad, d_pad) blocks, or
    one pinned dummy block."""
    eagg_map = ((lambda bi, t, l, d, j: (bi, t, l, j, 0)) if has_edge
                else (lambda bi, t, l, d, j: (0, 0, 0, 0, 0)))
    return ([_tile_spec(tn, k), _tile_spec(tn, k), _step_spec(n, d_pad),
             _tile_spec(tn, 1)],
            pl.BlockSpec((1, 1, 1, tn, d_pad), eagg_map))


# ------------------------------------------------------------------------
# EvolveGCN: weights-resident family. No node-resident recurrent state —
# the recurrence is over the per-layer GCN weights W_l^t, evolved by a
# matrix-GRU between snapshots (live-gated by the engine). The d axis
# blocks W's COLUMNS, which the matrix-GRU evolves independently (columns
# are the GRU batch), so per-(l, d-block) evolution is exact. Padding
# convention: all widths zero-padded into a common square d_pad; GRU
# params padded PER GATE BLOCK (ops._pad_matrix_gru_params); zero-padded
# weight ROWS stay zero under evolution per block (their gate inputs are
# identically 0), keeping junk activation columns out of valid output
# columns.

def _evolve_cell(has_edge, cached, eng, ins, outs, scr):
    (idx_ref, coef_ref, x_ref, mask_ref, _live, _w0, bg_ref, eagg_ref,
     _wx, _wh, _bp) = ins
    _gcn_layer(has_edge, eng, idx_ref, coef_ref, x_ref, mask_ref, eagg_ref,
               scr[1], scr[2] if cached else None,
               eng.state_block(scr, 0), bg_ref[0], outs[0])


def _evolve_evolve(eng, ins, scr):
    """Matrix-GRU evolution of W_l's (d) column block for step t+1, after
    the last tile of layer l consumed W_l^t. Identical math to
    rnn.matrix_gru on the valid region: W's columns are the GRU batch, so
    the block evolves independently; gate blocks split at d_pad (params
    padded per gate block by ops._pad_matrix_gru_params)."""
    wx_ref, wh_ref, bp_ref = ins[8], ins[9], ins[10]
    wt = eng.state_block(scr, 0).T                     # (td, d_pad)
    d = wt.shape[1]
    gx = _dot_exact(wt, wx_ref[0]) + bp_ref[0]
    gh = _dot_exact(wt, wh_ref[0])
    rx, zx, nx = gx[:, :d], gx[:, d:2 * d], gx[:, 2 * d:]
    rh, zh, nh = gh[:, :d], gh[:, d:2 * d], gh[:, 2 * d:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    nvec = jnp.tanh(nx + r * nh)
    eng.state_block_store(scr, 0, ((1.0 - z) * nvec + z * wt).T)


def _evolve_build(neigh_idx, neigh_coef, node_feat, node_mask, live,
                  w0, b_gcn, gru_wx, gru_wh, gru_b, edge_agg=None, *,
                  tn: int, td: Optional[int], residency: str = "vmem",
                  depth: int = 2):
    """Inputs pre-padded to the common square d_pad (a td multiple) by
    kernels/ops.py: node_feat (B, T, n, d_pad); w0 (B, L, d_pad, d_pad) —
    each stream's primed evolving weights, entering and leaving the chip
    exactly once per stream; gru params padded per gate block; live (B, T)
    int32 — 1 where the snapshot is real, 0 on no-op tail padding."""
    B, T, n, k = neigh_idx.shape
    L, d_pad = w0.shape[1], w0.shape[2]
    assert n % tn == 0
    paged = residency == "hbm_paged"
    td = d_pad if td is None else td
    assert d_pad % td == 0
    D = d_pad // td
    cached = D > 1 or paged
    grid = (B, T, L, D, n // tn)

    has_edge = edge_agg is not None
    if not has_edge:
        # one pinned (revisited) dummy block instead of (B,T,L,n,d_pad)
        # of streamed zeros; the kernel never reads it.
        edge_agg = jnp.zeros((1, 1, 1, tn, d_pad), node_feat.dtype)
    node_specs, eagg_spec = _layer_specs(n, tn, k, d_pad, has_edge)

    if paged:
        # HBM-resident evolving W, evolved IN PLACE in the aliased
        # (B, L, d_pad, d_pad) output: stage-in pulls layer l's (d) column
        # block into a (d_pad, td) staging window, the evolve hook updates
        # staging, write-back pushes it home. No read ring: the cell only
        # ever consumes its own (l, d) block, never the full width.
        w_in_spec = pl.BlockSpec(memory_space=pl.ANY)
        w_out_spec = pl.BlockSpec(memory_space=pl.ANY)
        states = (_StateMeta("weights", in_idx=5, out_idx=1, scr_idx=0,
                             sem_idx=3),)
        state_scratch = [pltpu.VMEM((d_pad, td), w0.dtype)]  # W staging
        sem_scratch = [pltpu.SemaphoreType.DMA((depth + 1,))]
        aliases = {5: 1}
    else:
        w_in_spec = pl.BlockSpec((1, 1, d_pad, d_pad),
                                 lambda bi, t, l, d, j: (bi, l, 0, 0))
        w_out_spec = pl.BlockSpec((1, 1, d_pad, td),
                                  lambda bi, t, l, d, j: (bi, l, 0, d))
        states = (_StateMeta("weights", in_idx=5, out_idx=1, scr_idx=0),)
        state_scratch = [pltpu.VMEM((L, d_pad, d_pad), w0.dtype)]
        sem_scratch = []
        aliases = {}

    layer_res3 = lambda bi, t, l, d, j: (l, 0, 0)
    meta = _Meta(
        n_in=11, n_out=2, states=states, live_idx=4, td=td, tn=tn,
        n_dblocks=D, paged=paged, depth=depth, g_rows=0)
    return _Launch(
        grid=grid,
        inputs=(neigh_idx, neigh_coef, node_feat, node_mask[..., None],
                live, w0, b_gcn[:, None], edge_agg, gru_wx, gru_wh,
                gru_b[:, None]),
        in_specs=[
            *node_specs,                                  # idx/coef/x/mask
            pl.BlockSpec(memory_space=pltpu.SMEM),        # live flags (B, T)
            w_in_spec,                                    # W0, per (b, l)
            pl.BlockSpec((1, 1, td),                      # GCN bias tile
                         lambda bi, t, l, d, j: (l, 0, d)),
            eagg_spec,                                    # edge agg
            pl.BlockSpec((1, d_pad, 3 * d_pad), layer_res3),  # GRU wx
            pl.BlockSpec((1, d_pad, 3 * d_pad), layer_res3),  # GRU wh
            pl.BlockSpec((1, 1, 3 * d_pad), layer_res3),      # GRU bias
        ],
        out_specs=[
            _out_tile_spec(tn, td),                       # per-step outputs
            w_out_spec,                                   # final weights
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, n, d_pad), node_feat.dtype),
            jax.ShapeDtypeStruct((B, L, d_pad, d_pad), w0.dtype),
        ],
        scratch=state_scratch + [
            pltpu.VMEM((2, n, d_pad), node_feat.dtype),   # activation planes
        ] + ([
            pltpu.VMEM((n, d_pad), node_feat.dtype),      # aggregation cache
        ] if cached else []) + sem_scratch,
        meta=meta,
        cell=functools.partial(_evolve_cell, has_edge, cached),
        evolve=_evolve_evolve,
        aliases=aliases,
    )


# ------------------------------------------------------------------------
# TGN (event-driven temporal GNN): the "event" temporal contract. The T
# grid axis sequences EVENT BATCHES, not snapshots — each step is a ragged
# batch of timestamped events laid out as ELL rows over the touched nodes
# (graph/events.pad_event_block), so ``lengths`` generalizes from ragged-T
# snapshot streams to ragged event streams. Per event batch, every touched
# node aggregates its event partners' t-1 memory plus a sinusoidal TIME
# ENCODING of the per-event timestamps (cos(t * freq_d), learnable per-dim
# frequencies — the TGAT/TGN functional form), feeds a GRU, and updates
# ONLY its own node-memory row (untouched rows carry over through the
# ping-pong copy-forward; padding rows scatter-drop). Dead (coef-0) event
# lanes contribute exactly zero to both aggregations, whatever timestamp
# they carry — the property tests pin this.

_TGN_MLOC, _TGN_CINP = 1, 3                  # scratch slots (both residencies)


def _tgn_cell(cached, eng, ins, outs, scr):
    (idx_ref, coef_ref, ts_ref, x_ref, _rowg, mask_ref, _m0,
     freq_ref, win_ref, wx_ref, wh_ref, b_ref) = ins
    out_ref = outs[0]
    mloc = scr[_TGN_MLOC]
    mask = mask_ref[0, 0]
    rows = eng.rows

    @pl.when(eng.step_start)
    def _gather():
        eng.gather_step(0, mloc)                # t-1 memory of touched nodes

    def _inputs():
        idx, coef, ts = idx_ref[0, 0], coef_ref[0, 0], ts_ref[0, 0]
        # sinusoidal time encoding per event lane; padded freq columns
        # give cos(0)=1 but only ever multiply zero-padded wx rows
        freq = freq_ref[...]                    # (1, d_pad)

        def _encode(kk, acc):
            return acc + _lane(coef, kk) * jnp.cos(_lane(ts, kk) * freq)

        agg_e = jax.lax.fori_loop(
            0, idx.shape[1], _encode,
            jnp.zeros((idx.shape[0], freq.shape[1]), jnp.float32))
        a = _ell_matrix(idx, coef, mloc.shape[0])
        xw = _dot_exact(x_ref[0, 0, rows, :], win_ref[...])
        return (xw + _dot_exact(a, mloc[...])) + agg_e

    if cached:  # D > 1 or paged: compute once per (t, j); d > 0 re-reads
        cinp = scr[_TGN_CINP]

        @pl.when(eng.first_dblock)
        def _fill_cache():
            cinp[rows] = _inputs()

        inp = cinp[rows]
    else:       # single d block: inline, no scratch round-trip
        inp = _inputs()

    td = eng.td
    gx = _dot_exact(inp, wx_ref[0]) + b_ref[0]
    gh = _dot_exact(mloc[rows] * mask, wh_ref[0])
    rx, zx, nx = gx[:, :td], gx[:, td:2 * td], gx[:, 2 * td:]
    rh, zh, nh = gh[:, :td], gh[:, td:2 * td], gh[:, 2 * td:]
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    nn = jnp.tanh(nx + r * nh)
    m_own = mloc[rows, eng.blk] * mask
    m_new = ((1.0 - z) * nn + z * m_own) * mask
    eng.scatter_tile(0, m_new)
    out_ref[0, 0] = m_new


def _tgn_build(neigh_idx, neigh_coef, neigh_ts, node_feat, row_gidx,
               node_mask, mem0, freq, w_in, wx, wh, b, *,
               tn: int, td: Optional[int], residency: str = "vmem",
               depth: int = 2):
    """Event-stream launch: (B, T, n, k) ELL event batches (local partner
    ids) with per-lane timestamps; the node-memory store (B, G, h) is the
    single pingpong state, entering and leaving the chip once per stream."""
    B, T, n, k = neigh_idx.shape
    din, h = node_feat.shape[3], mem0.shape[2]
    G = mem0.shape[1]
    assert n % tn == 0
    paged = residency == "hbm_paged"
    td = h if td is None else td
    d_pad = _round_up(h, td)
    D = d_pad // td
    cached = D > 1 or paged
    grid = (B, T, 1, D, n // tn)

    freq_p = _pad_dim(freq, d_pad, 0)[None]           # (1, d_pad): 2-D ref
    win_p = _pad_dim(w_in, d_pad, -1)
    wxp = _pack_gate_blocks(_pad_dim(wx, d_pad, 0), 3, td)  # (D, d_pad, 3td)
    whp = _pack_gate_blocks(_pad_dim(wh, d_pad, 0), 3, td)  # (D, d_pad, 3td)
    bp = _pack_gate_bias(b, 3, td)                          # (D, 1, 3td)
    m_in, m_in_spec, m_out_spec, m_out_shape = _node_state_io(
        mem0, d_pad, td, True, paged)

    tables = [
        pltpu.VMEM((n, d_pad), mem0.dtype),           # t-1 memory row table
        pltpu.VMEM((tn, td), mem0.dtype),             # row-staging tile
    ] + ([
        pltpu.VMEM((n, d_pad), node_feat.dtype),      # GRU-input cache
    ] if cached else [])
    if paged:
        # HBM-resident memory store as an A/B plane pair, aliased in-place
        # onto its output.
        states = (_StateMeta("pingpong", in_idx=6, out_idx=1, scr_idx=0,
                             ring_idx=4, sem_idx=5),)
        scratch = [pltpu.VMEM((G, td), mem0.dtype)] + tables + [  # staging
            pltpu.VMEM((depth, G, td), mem0.dtype),               # read ring
            pltpu.SemaphoreType.DMA((depth + 1,)),
        ]
        aliases = {6: 1}
    else:
        states = (_StateMeta("pingpong", in_idx=6, out_idx=1, scr_idx=0),)
        scratch = [pltpu.VMEM((2, G, d_pad), mem0.dtype)] + tables  # planes
        aliases = {}

    res2 = lambda bi, t, l, d, j: (0, 0)
    meta = _Meta(
        n_in=12, n_out=2, states=states, live_idx=None, td=td, tn=tn,
        n_dblocks=D, temporal="event", paged=paged, depth=depth, g_rows=G,
        rows_idx=4, stage_idx=2)
    return _Launch(
        grid=grid,
        inputs=(neigh_idx, neigh_coef, neigh_ts, node_feat,
                row_gidx[:, :, None, :], node_mask[..., None], m_in, freq_p,
                win_p, wxp, whp, bp),
        in_specs=[
            _tile_spec(tn, k),                        # partner ids (local)
            _tile_spec(tn, k),                        # event coef (1/deg)
            _tile_spec(tn, k),                        # event timestamps
            _step_spec(n, din),                       # touched-node features
            _row_ids_spec(n),                         # global row ids (SMEM)
            _tile_spec(tn, 1),                        # node_mask column
            m_in_spec,                                # mem0 / mem plane pair
            pl.BlockSpec((1, d_pad), res2),           # time-enc frequencies
            pl.BlockSpec((din, d_pad), res2),         # input projection
            *_gate_specs(d_pad, 3, td, d_pad),        # wx / wh / bias tiles
        ],
        out_specs=[
            _out_tile_spec(tn, td),                   # per-batch mem outputs
            m_out_spec,                               # final memory
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, n, d_pad), node_feat.dtype),
            m_out_shape,
        ],
        scratch=scratch,
        meta=meta,
        cell=functools.partial(_tgn_cell, cached),
        evolve=None,
        aliases=aliases,
    )


# ------------------------------------------------------------------------
# Static GCN (GenGNN-style): the "static" temporal contract — no
# recurrence, zero StateDefs, no evolve hook; the engine's state
# init/copy-forward/drain loops are vacuously empty. T must be 1:
# independent snapshots fold onto the B axis instead (the serve express
# lane), so a "stream" of static graphs is just a batch. The L grid axis
# sequences the multi-layer GCN over the shared activation-plane layer
# body, but the per-layer weights come straight from INPUT refs
# (BlockSpec-indexed by (l, d)) — nothing is resident across steps.

def _static_cell(has_edge, cached, eng, ins, outs, scr):
    (idx_ref, coef_ref, x_ref, mask_ref, w_ref, bg_ref, eagg_ref) = ins
    _gcn_layer(has_edge, eng, idx_ref, coef_ref, x_ref, mask_ref, eagg_ref,
               scr[0], scr[1] if cached else None, w_ref[0], bg_ref[0],
               outs[0])


def _static_build(neigh_idx, neigh_coef, node_feat, node_mask,
                  weights, b_gcn, edge_agg=None, *,
                  tn: int, td: Optional[int], residency: str = "vmem",
                  depth: int = 2):
    """Inputs pre-padded to the common square d_pad by kernels/ops.py:
    node_feat (B, 1, n, d_pad); weights (L, d_pad, d_pad) stacked per
    layer, SHARED across the batch (params, not state)."""
    if residency != "vmem":
        raise ValueError(
            "static_gcn has no state to page; residency must be 'vmem'")
    B, T, n, k = neigh_idx.shape
    if T != 1:
        raise ValueError(
            f"static family runs with T == 1, got T={T}: a static-GCN "
            "'stream' has no recurrence — fold independent snapshots onto "
            "the batch axis instead (core.gcn.StaticGCN.step_stream does)")
    L, d_pad = weights.shape[0], weights.shape[1]
    assert n % tn == 0
    td = d_pad if td is None else td
    assert d_pad % td == 0
    D = d_pad // td
    grid = (B, 1, L, D, n // tn)

    has_edge = edge_agg is not None
    if not has_edge:
        # one pinned (revisited) dummy block; the kernel never reads it.
        edge_agg = jnp.zeros((1, 1, 1, tn, d_pad), node_feat.dtype)
    node_specs, eagg_spec = _layer_specs(n, tn, k, d_pad, has_edge)

    meta = _Meta(
        n_in=7, n_out=1, states=(), live_idx=None, td=td, tn=tn,
        n_dblocks=D, temporal="static")
    return _Launch(
        grid=grid,
        inputs=(neigh_idx, neigh_coef, node_feat, node_mask[..., None],
                weights, b_gcn[:, None], edge_agg),
        in_specs=[
            *node_specs,                                  # idx/coef/x/mask
            pl.BlockSpec((1, d_pad, td),                  # W_l column block
                         lambda bi, t, l, d, j: (l, 0, d)),
            pl.BlockSpec((1, 1, td),                      # GCN bias tile
                         lambda bi, t, l, d, j: (l, 0, d)),
            eagg_spec,                                    # edge agg
        ],
        out_specs=[
            _out_tile_spec(tn, td),                       # per-snapshot outs
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n, d_pad), node_feat.dtype),
        ],
        scratch=[
            pltpu.VMEM((2, n, d_pad), node_feat.dtype),   # activation planes
        ] + ([
            pltpu.VMEM((n, d_pad), node_feat.dtype),      # aggregation cache
        ] if D > 1 else []),
        meta=meta,
        cell=functools.partial(_static_cell, has_edge, D > 1),
        evolve=None,
    )


# ------------------------------------------------------------------------
# The registry: every DGNN family the stream engine serves. Adding a
# family = registering a cell spec here (CI runs the registry tests for
# every entry, so an untested spec fails the build).

REGISTRY: dict[str, CellSpec] = {
    "gcrn": CellSpec(
        name="gcrn",
        resident="node-state store: h (ping-pong pair) + c (own-row)",
        states=(StateDef("h", "pingpong", full_read=True),
                StateDef("c", "row")),
        build=_gcrn_build,
        temporal="dense"),
    "stacked": CellSpec(
        name="stacked",
        resident="node-state store: h (own-row)",
        states=(StateDef("h", "row", full_read=True),),
        build=_stacked_build,
        temporal="dense"),
    "evolve": CellSpec(
        name="evolve",
        resident="per-layer evolving weights W_l (matrix-GRU in-kernel)",
        states=(StateDef("weights", "weights"),),
        build=_evolve_build,
        temporal="dense"),
    "tgn": CellSpec(
        name="tgn",
        resident="node-memory store: mem (ping-pong pair)",
        states=(StateDef("mem", "pingpong", full_read=True),),
        build=_tgn_build,
        temporal="event"),
    "static_gcn": CellSpec(
        name="static_gcn",
        resident="none (stateless; activation ping-pong scratch only)",
        states=(),
        build=_static_build,
        temporal="static"),
}


def _validate_registry() -> None:
    """Structural invariants on the declarative temporal contract,
    checked once at import: a spec that lies about its mode fails before
    any launch does."""
    for name, spec in REGISTRY.items():
        if spec.temporal not in TEMPORAL_MODES:
            raise ValueError(
                f"family {name!r} declares unknown temporal mode "
                f"{spec.temporal!r}; expected one of {TEMPORAL_MODES}")
        if spec.temporal == "static" and spec.states:
            raise ValueError(
                f"static family {name!r} must declare zero StateDefs, "
                f"got {[s.name for s in spec.states]}")
        if spec.temporal != "static" and not spec.states:
            raise ValueError(
                f"{spec.temporal} family {name!r} declares no StateDefs: "
                "recurrence without state is a contract violation")


_validate_registry()
