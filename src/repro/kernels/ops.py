"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively (Mosaic). On the CPU backend — and
only there — they run in interpret mode, which executes the kernel body
with the same tiling: the correctness contract the CPU tests rely on.
``force_ref=True`` routes to the pure-jnp oracle (used by the XLA
production path when the Pallas path is not profitable, e.g. tiny
snapshots under vmap).

Ragged node counts are handled here: row-tiled inputs are auto-padded to
the node tile ``tn`` (the sink-row coef-0 convention of graph/padding.py:
padded lanes carry coef 0, padded rows are sliced off the outputs), so
callers never need ``n % tn == 0``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import csr_spmm as _spmm
from repro.kernels import dgnn_fused as _fused
from repro.kernels import fused_rnn as _rnn
from repro.kernels import ref as _ref
from repro.kernels import stream_fused as _stream


def _interpret() -> bool:
    """Pallas interpret mode on the CPU backend only: an accelerator never
    silently runs the interpreter in place of its compiled kernels."""
    return jax.default_backend() == "cpu"


_FORCE_REF = False


def set_force_ref(flag: bool) -> None:
    """Route ALL kernel wrappers to the pure-jnp oracles (the XLA
    production path) until reset. Benchmarks flip this on CPU hosts, where
    interpret-mode Pallas wall time measures the interpreter rather than
    the dataflow; per-call ``force_ref=True`` stays available for targeted
    use. Affects functions traced AFTER the flip (jit caches keep whatever
    path they captured)."""
    global _FORCE_REF
    _FORCE_REF = flag


# ------------------------------------------------------- fault hook ----
# Launch-site fault injection for the serve engine's chaos harness
# (serve/faults.py). When a hook is installed at TRACE time, the stream
# dispatch embeds an io_callback ahead of the launch, so the hook fires
# at RUN time on every execution of the jitted program — a raised fault
# fails the real launch (wrapped in the backend's callback error), and a
# sleeping hook delays it (deadline tests). The embedded callback reads
# the CURRENT hook on each run, so restoring the hook to None turns
# already-traced programs back into no-ops.

_FAULT_HOOK = None


def set_fault_hook(hook):
    """Install (or clear, with None) the stream-launch fault hook:
    ``hook(family=..., batched=..., force_ref=...)``, called inside every
    stream-engine dispatch. Returns the previous hook so callers can
    scope the installation (the serve engine installs around its run
    loops). Chaos testing only — never installed in production paths."""
    global _FAULT_HOOK
    prev, _FAULT_HOOK = _FAULT_HOOK, hook
    return prev


def _call_fault_hook(family: str, batched: bool, force_ref: bool):
    import numpy as np

    hook = _FAULT_HOOK
    if hook is not None:
        hook(family=family, batched=batched, force_ref=force_ref)
    return np.int32(0)


def _with_fault_probe(run, family: str, batched: bool, force_ref: bool):
    """Sequence the fault hook into the traced program (io_callback: runs
    every execution, never DCE'd). Deliberately NOT ``ordered=True``:
    every serve launch is synchronous (block_until_ready before the next),
    and the ordered token chain would carry a failed probe's error into a
    LATER healthy launch — exactly the cross-launch contamination the
    fault-isolation layer must not manufacture itself."""
    from jax.experimental import io_callback

    def probed(*a):
        io_callback(
            lambda: _call_fault_hook(family, batched, force_ref),
            jax.ShapeDtypeStruct((), jnp.int32))
        return run(*a)

    return probed


# single shared copies of the round-up / constant-fill padding helpers
# (stream_fused owns them; ops re-exports under its historical names)
_pad_to = _stream._pad_dim


def _pad_rows(n: int, tn: int) -> int:
    return _stream._round_up(n, tn)


def ell_spmm(neigh_idx, neigh_coef, neigh_eidx, x, edge_msg=None, *,
             tn: int = 128, force_ref: bool = False):
    if force_ref or _FORCE_REF:
        return _ref.ell_spmm(neigh_idx, neigh_coef, neigh_eidx, x, edge_msg)
    n = neigh_idx.shape[0]
    n2 = _pad_rows(n, tn)
    out = _spmm.ell_spmm_pallas(
        _pad_to(neigh_idx, n2, 0), _pad_to(neigh_coef, n2, 0),
        _pad_to(neigh_eidx, n2, 0), x,
        edge_msg, tn=tn, interpret=_interpret())
    return out[:n]


def fused_gru(x, h, wx, wh, b, *, tb: int = 128, force_ref: bool = False):
    if force_ref or _FORCE_REF:
        return _ref.fused_gru(x, h, wx, wh, b)
    return _rnn.fused_gru_pallas(x, h, wx, wh, b, tb=tb, interpret=_interpret())


def fused_lstm(x, h, c, wx, wh, b, *, tb: int = 128, force_ref: bool = False):
    if force_ref or _FORCE_REF:
        return _ref.fused_lstm(x, h, c, wx, wh, b)
    return _rnn.fused_lstm_pallas(x, h, c, wx, wh, b, tb=tb, interpret=_interpret())


def dgnn_fused_step(neigh_idx, neigh_coef, neigh_eidx, x, h, c, wx, wh, b,
                    edge_msg=None, *, tn: int = 128, force_ref: bool = False):
    if force_ref or _FORCE_REF:
        return _ref.dgnn_fused_step(neigh_idx, neigh_coef, neigh_eidx, x, h, c,
                                    wx, wh, b, edge_msg)
    n = neigh_idx.shape[0]
    n2 = _pad_rows(n, tn)
    h_new, c_new = _fused.gcrn_fused_pallas(
        _pad_to(neigh_idx, n2, 0), _pad_to(neigh_coef, n2, 0),
        _pad_to(neigh_eidx, n2, 0), x, h, _pad_to(c, n2, 0),
        wx, wh, b, edge_msg, tn=tn, interpret=_interpret())
    return h_new[:n], c_new[:n]


def stacked_fused_step(neigh_idx, neigh_coef, neigh_eidx, x, h, w_gcn, b_gcn,
                       wx, wh, b, edge_msg=None, *, tn: int = 128,
                       force_ref: bool = False):
    if force_ref or _FORCE_REF:
        return _ref.stacked_fused_step(neigh_idx, neigh_coef, neigh_eidx, x, h,
                                       w_gcn, b_gcn, wx, wh, b, edge_msg)
    n = neigh_idx.shape[0]
    n2 = _pad_rows(n, tn)
    out = _fused.stacked_fused_pallas(
        _pad_to(neigh_idx, n2, 0), _pad_to(neigh_coef, n2, 0),
        _pad_to(neigh_eidx, n2, 0), x, _pad_to(h, n2, 0),
        w_gcn, b_gcn, wx, wh, b, edge_msg, tn=tn, interpret=_interpret())
    return out[:n]


# ------------------------------------------------------------ V3 stream ----
# ONE pair of public entry points — stream_steps / stream_steps_batched —
# dispatching through the stream-engine registry (stream_fused.REGISTRY)
# by family name instead of family-named wrappers. The force-ref gate sits
# at this single entry, so no family branch can silently run the Pallas
# path under force-ref (the regression tests/test_registry.py pins).

def _pad_stream(neigh_idx, neigh_coef, neigh_eidx, node_feat, renumber,
                node_mask, tn: int):
    """Auto-pad the node axis of a (..., n, k)/(..., n) snapshot stream.

    Works for both the single-stream (T, n, ...) and the batched
    (B, T, n, ...) layouts: the node axis is always -2 on the ELL/feature
    arrays and -1 on the per-node row arrays.
    """
    n = neigh_idx.shape[-2]
    n2 = _pad_rows(n, tn)
    return (n,
            _pad_to(neigh_idx, n2, -2), _pad_to(neigh_coef, n2, -2),
            _pad_to(neigh_eidx, n2, -2), _pad_to(node_feat, n2, -2),
            _pad_to(renumber, n2, -1, fill=-1), _pad_to(node_mask, n2, -1))


def _row_table(renumber, n_global: int):
    """Global store row of each local node (``n_global``, the drop
    sentinel, on padding rows) — the kernel's per-step SMEM row-id table.
    Leading axes (T,) or (B, T) pass through untouched."""
    return jnp.where(renumber >= 0, renumber, n_global).astype(jnp.int32)


def edge_project(edge_feat, w_edge):
    """Per-edge messages ``edge_feat @ w_edge`` of the stream engine's
    edge term, under the named scope ``edge_project`` (op metadata only:
    a profile's device ops carry the name)."""
    with jax.named_scope("edge_project"):
        return edge_feat @ w_edge


def edge_aggregate(coef, eidx, edge_msg):
    """Pre-aggregated ELL edge-message term ``sum_k coef[..., k] *
    edge_msg[eidx[..., k]]`` of shape (..., n, width): additive in the
    aggregation, so it factors out of the stream kernel, which then only
    aggregates node rows. ``edge_msg`` is (..., e, width) with the same
    leading axes as ``eidx`` (..., n, k). Runs under the named scope
    ``edge_aggregate``, which names its gather in a profile."""
    with jax.named_scope("edge_aggregate"):
        lead = eidx.shape[:-2]
        n, k = eidx.shape[-2:]
        g = jnp.take_along_axis(edge_msg, eidx.reshape(*lead, n * k, 1),
                                axis=-2)
        g = g.reshape(*lead, n, k, edge_msg.shape[-1])
        return (g * coef[..., None]).sum(axis=-2)


def _gcrn_launch(batched, neigh_idx, neigh_coef, neigh_eidx, node_feat,
                 renumber, node_mask, h0, c0, wx, wh, b, edge_msg=None, *,
                 tn: int, td, residency: str = "vmem", depth: int = 2):
    """Pad/pack + engine launch for the integrated (GC-LSTM) family."""
    if not batched:
        em = None if edge_msg is None else edge_msg[None]
        outs, hT, cT = _gcrn_launch(
            True, neigh_idx[None], neigh_coef[None], neigh_eidx[None],
            node_feat[None], renumber[None], node_mask[None], h0[None],
            c0[None], wx, wh, b, em, tn=tn, td=td, residency=residency,
            depth=depth)
        return outs[0], hT[0], cT[0]
    n, idx, coef, eidx, x, ren, mask = _pad_stream(
        neigh_idx, neigh_coef, neigh_eidx, node_feat, renumber, node_mask, tn)
    eagg = None if edge_msg is None else edge_aggregate(coef, eidx, edge_msg)
    h = h0.shape[-1]
    outs, hT, cT = _stream.stream_call(
        "gcrn", idx, coef, x, _row_table(ren, h0.shape[1]), mask, h0, c0,
        wx, wh, b, eagg, tn=tn, td=td, interpret=_interpret(),
        residency=residency, depth=depth)
    return outs[:, :, :n, :h], hT[..., :h], cT[..., :h]


def _stacked_launch(batched, neigh_idx, neigh_coef, neigh_eidx, node_feat,
                    renumber, node_mask, h0, w_gcn, b_gcn, wx, wh, b,
                    edge_msg=None, *, tn: int, td,
                    residency: str = "vmem", depth: int = 2):
    """Pad/pack + engine launch for the stacked (GCN -> GRU) family."""
    if not batched:
        em = None if edge_msg is None else edge_msg[None]
        outs, hT = _stacked_launch(
            True, neigh_idx[None], neigh_coef[None], neigh_eidx[None],
            node_feat[None], renumber[None], node_mask[None], h0[None],
            w_gcn, b_gcn, wx, wh, b, em, tn=tn, td=td,
            residency=residency, depth=depth)
        return outs[0], hT[0]
    n, idx, coef, eidx, x, ren, mask = _pad_stream(
        neigh_idx, neigh_coef, neigh_eidx, node_feat, renumber, node_mask, tn)
    eagg = None if edge_msg is None else edge_aggregate(coef, eidx, edge_msg)
    h = h0.shape[-1]
    outs, hT = _stream.stream_call(
        "stacked", idx, coef, x, _row_table(ren, h0.shape[1]), mask, h0,
        w_gcn, b_gcn, wx, wh, b, eagg, tn=tn, td=td, interpret=_interpret(),
        residency=residency, depth=depth)
    return outs[:, :, :n, :h], hT[..., :h]


# ---------------------------------------- V3 weights-resident stream ----

def _pad_matrix_gru_params(wx, wh, b, dmax: int):
    """Zero-pad square matrix-GRU cell params (din -> din) to dmax PER
    GATE BLOCK, so the padded cell splits its gates at dmax boundaries
    and the valid region evolves exactly as the unpadded cell. Padded
    weight ROWS evolve to zero under the padded cell (their gate inputs
    are identically zero), which is the invariant the kernel's padded
    matmuls rely on."""
    def pad_gates(m):
        blocks = jnp.split(m, 3, axis=1)
        return jnp.concatenate(
            [_pad_to(_pad_to(g, dmax, 0), dmax, 1) for g in blocks], axis=1)

    b3 = jnp.split(b, 3)
    return (pad_gates(wx), pad_gates(wh),
            jnp.concatenate([_pad_to(g, dmax, 0) for g in b3]))


def _stack_padded(mats, dmax: int, batched: bool):
    """Stack per-layer (optionally per-stream) matrices into one
    (L, dmax, dmax) / (B, L, dmax, dmax) zero-padded array."""
    axis = 1 if batched else 0
    return jnp.stack([_pad_to(_pad_to(w, dmax, -2), dmax, -1) for w in mats],
                     axis=axis)


def _evolve_pack(neigh_idx, neigh_coef, node_feat, node_mask, weights,
                 b_gcn, gru_wx, gru_wh, gru_b, edge_aggs, tn: int,
                 td, batched: bool):
    """Shared padding/packing for the weights-resident stream family. All
    layer widths are zero-padded into one common square ``dmax`` (rounded
    up to a ``td`` multiple so the engine's d axis tiles it evenly)."""
    n = neigh_idx.shape[-2]
    n2 = _pad_rows(n, tn)
    dims = [(w.shape[-2], w.shape[-1]) for w in weights]
    dmax = max(max(d) for d in dims)
    if td is not None:
        dmax = ((dmax + td - 1) // td) * td
    idx = _pad_to(neigh_idx, n2, -2)
    coef = _pad_to(neigh_coef, n2, -2)
    x = _pad_to(_pad_to(node_feat, n2, -2), dmax, -1)
    mask = _pad_to(node_mask, n2, -1)
    w0 = _stack_padded(weights, dmax, batched)
    bg = jnp.stack([_pad_to(bb, dmax, 0) for bb in b_gcn])
    if edge_aggs is None:
        eagg = None  # static has_edge=False specialization in the kernel
    else:
        eagg = jnp.stack(
            [_pad_to(_pad_to(ea, n2, -2), dmax, -1) for ea in edge_aggs],
            axis=-3)
    gwx, gwh, gb = zip(*[_pad_matrix_gru_params(wx, wh, bb, dmax)
                         for wx, wh, bb in zip(gru_wx, gru_wh, gru_b)])
    return (n, dims, idx, coef, x, mask, w0, bg, eagg,
            jnp.stack(gwx), jnp.stack(gwh), jnp.stack(gb))


def _evolve_unpack(outs, wT, n: int, dims, out_dim: int, batched: bool):
    """Slice kernel-padded outputs/weights back to their true shapes."""
    outs = outs[..., :n, :out_dim]
    sl = (slice(None),) if batched else ()
    weights = tuple(wT[sl + (i, slice(0, di), slice(0, do))]
                    for i, (di, do) in enumerate(dims))
    return outs, weights


def _evolve_launch(batched, neigh_idx, neigh_coef, node_feat, node_mask,
                   live, weights, b_gcn, gru_wx, gru_wh, gru_b,
                   edge_aggs=None, *, tn: int, td,
                   residency: str = "vmem", depth: int = 2):
    """Pad/pack + engine launch for the weights-evolved family.

    ``weights``/``b_gcn``/``gru_*`` are per-layer lists (true, unpadded
    shapes; batched adds a leading B axis to ``weights`` leaves);
    ``edge_aggs`` is the per-layer pre-aggregated edge-message term or
    None; ``live`` gates the in-kernel matrix-GRU evolution so no-op tail
    snapshots leave the weights untouched."""
    if not batched:
        ea = None if edge_aggs is None else [a[None] for a in edge_aggs]
        outs, wT = _evolve_launch(
            True, neigh_idx[None], neigh_coef[None], node_feat[None],
            node_mask[None], jnp.asarray(live)[None],
            [w[None] for w in weights], b_gcn, gru_wx, gru_wh, gru_b, ea,
            tn=tn, td=td, residency=residency, depth=depth)
        return outs[0], tuple(w[0] for w in wT)
    n, dims, idx, coef, x, mask, w0, bg, eagg, gwx, gwh, gb = _evolve_pack(
        neigh_idx, neigh_coef, node_feat, node_mask, weights, b_gcn,
        gru_wx, gru_wh, gru_b, edge_aggs, tn, td, batched=True)
    outs, wT = _stream.stream_call(
        "evolve", idx, coef, x, mask, jnp.asarray(live, jnp.int32), w0, bg,
        gwx, gwh, gb, eagg, tn=tn, td=td, interpret=_interpret(),
        residency=residency, depth=depth)
    return _evolve_unpack(outs, wT, n, dims, dims[-1][1], batched=True)


# ----------------------------------------- temporal-contract launchers ----

def _tgn_launch(batched, neigh_idx, neigh_coef, neigh_ts, node_feat,
                renumber, node_mask, mem0, freq, w_in, wx, wh, b, *,
                tn: int, td, residency: str = "vmem", depth: int = 2):
    """Pad/pack + engine launch for the event-stream (TGN) family.

    The T axis sequences EVENT BATCHES (graph/events.pad_event_block):
    ``neigh_ts`` carries per-event-lane timestamps in the slot dense
    families use for edge indices — same (..., n, k) shape, validated
    here, zero on dead lanes (their coef is 0, so the time encoding of a
    padded event contributes exactly zero)."""
    if neigh_ts.shape != neigh_idx.shape:
        raise ValueError(
            f"tgn event timestamps must match the ELL lane shape: "
            f"ts {neigh_ts.shape} vs idx {neigh_idx.shape}")
    if not jnp.issubdtype(jnp.asarray(neigh_ts).dtype, jnp.floating):
        raise ValueError(
            f"tgn event timestamps must be floating, got "
            f"{jnp.asarray(neigh_ts).dtype}")
    if not batched:
        outs, memT = _tgn_launch(
            True, neigh_idx[None], neigh_coef[None], neigh_ts[None],
            node_feat[None], renumber[None], node_mask[None], mem0[None],
            freq, w_in, wx, wh, b, tn=tn, td=td, residency=residency,
            depth=depth)
        return outs[0], memT[0]
    # ts rides the eidx slot of the shared padder (same node-axis layout)
    n, idx, coef, ts, x, ren, mask = _pad_stream(
        neigh_idx, neigh_coef, neigh_ts, node_feat, renumber, node_mask, tn)
    h = mem0.shape[-1]
    outs, memT = _stream.stream_call(
        "tgn", idx, coef, ts, x, _row_table(ren, mem0.shape[1]), mask, mem0,
        freq, w_in, wx, wh, b, tn=tn, td=td, interpret=_interpret(),
        residency=residency, depth=depth)
    return outs[:, :, :n, :h], memT[..., :h]


def _static_pack(neigh_idx, neigh_coef, node_feat, node_mask, weights,
                 b_gcn, edge_aggs, tn: int, td):
    """Padding/packing for the static (no-recurrence) family: the same
    common-square ``dmax`` layout as the weights-evolved pack, minus the
    GRU params and the live flag — weights are shared params, not
    per-stream state."""
    n = neigh_idx.shape[-2]
    n2 = _pad_rows(n, tn)
    dims = [(w.shape[-2], w.shape[-1]) for w in weights]
    dmax = max(max(d) for d in dims)
    if td is not None:
        dmax = ((dmax + td - 1) // td) * td
    idx = _pad_to(neigh_idx, n2, -2)
    coef = _pad_to(neigh_coef, n2, -2)
    x = _pad_to(_pad_to(node_feat, n2, -2), dmax, -1)
    mask = _pad_to(node_mask, n2, -1)
    w = _stack_padded(weights, dmax, batched=False)    # (L, dmax, dmax)
    bg = jnp.stack([_pad_to(bb, dmax, 0) for bb in b_gcn])
    if edge_aggs is None:
        eagg = None  # static has_edge=False specialization in the kernel
    else:
        eagg = jnp.stack(
            [_pad_to(_pad_to(ea, n2, -2), dmax, -1) for ea in edge_aggs],
            axis=-3)
    return n, dims, idx, coef, x, mask, w, bg, eagg


def _static_launch(batched, neigh_idx, neigh_coef, node_feat, node_mask,
                   weights, b_gcn, edge_aggs=None, *, tn: int, td,
                   residency: str = "vmem", depth: int = 2):
    """Pad/pack + engine launch for the static (no-recurrence) family.

    T must be 1 on the engine path (the kernel raises otherwise):
    independent snapshots fold onto the batch axis, which is what makes
    the serve express lane a plain co-batched launch with no state
    checkpointing. Returns a 1-tuple ``(outs,)`` — zero final states."""
    if not batched:
        ea = None if edge_aggs is None else [a[None] for a in edge_aggs]
        (outs,) = _static_launch(
            True, neigh_idx[None], neigh_coef[None], node_feat[None],
            node_mask[None], weights, b_gcn, ea, tn=tn, td=td,
            residency=residency, depth=depth)
        return (outs[0],)
    n, dims, idx, coef, x, mask, w, bg, eagg = _static_pack(
        neigh_idx, neigh_coef, node_feat, node_mask, weights, b_gcn,
        edge_aggs, tn, td)
    (outs,) = _stream.stream_call(
        "static_gcn", idx, coef, x, mask, w, bg, eagg,
        tn=tn, td=td, interpret=_interpret(), residency=residency,
        depth=depth)
    return (outs[..., :n, :dims[-1][1]],)


# ------------------------------------------------- unified stream entry ----
# family name -> ((solo oracle, batched oracle), engine launcher,
# batched-arg index set, ragged-axis index map). The oracle column is the
# XLA production path; the launcher column pads, packs, and dispatches
# through stream_fused.REGISTRY. The batched-arg set lists the positional
# args whose leaves carry a leading B axis (DeviceSpec shards exactly
# those); the ragged map names the (coef, mask, renumber, live) arg
# positions the per-stream ``lengths`` masking rewrites.

_STREAM_DISPATCH = {
    "gcrn": ((_ref.gcrn_stream_ref, _ref.gcrn_stream_batched_ref),
             _gcrn_launch, frozenset(range(8)) | {11},
             dict(coef=1, mask=5, ren=4, live=None)),
    "stacked": ((_ref.stacked_stream_ref, _ref.stacked_stream_batched_ref),
                _stacked_launch, frozenset(range(7)) | {12},
                dict(coef=1, mask=5, ren=4, live=None)),
    "evolve": ((_ref.evolve_stream_ref, _ref.evolve_stream_batched_ref),
               _evolve_launch, frozenset(range(6)) | {10},
               dict(coef=1, mask=3, ren=None, live=4)),
    "tgn": ((_ref.tgn_stream_ref, _ref.tgn_stream_batched_ref),
            _tgn_launch, frozenset(range(7)),
            dict(coef=1, mask=5, ren=4, live=None)),
    "static_gcn": ((_ref.static_gcn_stream_ref,
                    _ref.static_gcn_stream_batched_ref),
                   _static_launch, frozenset(range(4)) | {6},
                   dict(coef=1, mask=3, ren=None, live=None)),
}


def stream_families() -> tuple:
    """Families servable by the stream engine (== stream_fused.REGISTRY)."""
    return tuple(sorted(_STREAM_DISPATCH))


def family_temporal(family: str) -> str:
    """The family's declared time semantics ("dense" | "event" |
    "static") from its registry cell spec — the single source of truth
    the plan layer and the serve engine read instead of assuming
    dense-T."""
    if family not in _stream.REGISTRY:
        raise KeyError(f"unknown stream-engine family {family!r}; "
                       f"registered: {stream_families()}")
    return _stream.REGISTRY[family].temporal


def _apply_lengths(family: str, args: tuple, lengths) -> tuple:
    """Turn the T tail of each stream in a (B, T, ...) batch into no-op
    snapshots: steps t >= lengths[b] get coef 0 / mask 0 / renumber -1
    (and live 0 for weights-evolved families), which is exactly the
    empty-snapshot no-op contract the engine already honours — so the tail
    CONTENT is irrelevant and callers can pad ragged streams with anything
    shape-compatible instead of manufacturing empty snapshots."""
    axes = _STREAM_DISPATCH[family][3]
    lengths = jnp.asarray(lengths, jnp.int32)
    coef = args[axes["coef"]]
    t_axis = jnp.arange(coef.shape[1], dtype=jnp.int32)
    live = t_axis[None, :] < lengths[:, None]          # (B, T)
    out = list(args)
    out[axes["coef"]] = jnp.asarray(coef) * live[:, :, None, None]
    mi = axes["mask"]
    out[mi] = jnp.asarray(args[mi]) * live[:, :, None]
    if axes["ren"] is not None:
        ri = axes["ren"]
        out[ri] = jnp.where(live[:, :, None], jnp.asarray(args[ri]), -1)
    if axes["live"] is not None:
        li = axes["live"]
        out[li] = jnp.asarray(args[li]) * live.astype(jnp.int32)
    return tuple(out)


def _shard_batch(family: str, run, args, device):
    """Wrap a batched stream launch in shard_map over the DeviceSpec mesh:
    the leading B grid axis splits across devices (streams are
    independent — no collectives), shared params replicate. Covers the
    Pallas engine AND the force-ref oracle path identically."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_stream_mesh

    B = args[0].shape[0]
    if B % device.n_devices:
        raise ValueError(
            f"stream batch B={B} not divisible by DeviceSpec.n_devices="
            f"{device.n_devices}")
    batch_args = _STREAM_DISPATCH[family][2]
    in_specs = tuple(P(device.axis) if i in batch_args else P()
                     for i in range(len(args)))
    return jax.shard_map(run, mesh=make_stream_mesh(device),
                         in_specs=in_specs, out_specs=P(device.axis),
                         check_vma=False)


def _stream_dispatch(family: str, batched: bool, args, kwargs, *, tn, td,
                     force_ref, lengths=None, device=None,
                     residency: str = "vmem", depth: int = 2):
    if family not in _STREAM_DISPATCH:
        raise KeyError(f"unknown stream-engine family {family!r}; "
                       f"registered: {stream_families()}")
    oracles, launch = _STREAM_DISPATCH[family][:2]
    if batched and lengths is not None:
        args = _apply_lengths(family, args, lengths)
    ref = bool(force_ref or _FORCE_REF)
    if ref:
        # single force-ref gate for EVERY family and batching mode: the
        # engine launcher (and thus pallas_call) is unreachable from here.
        run = lambda *a: oracles[1 if batched else 0](*a, **kwargs)
    else:
        run = lambda *a: launch(batched, *a, **kwargs, tn=tn, td=td,
                                residency=residency, depth=depth)
    if _FAULT_HOOK is not None:
        run = _with_fault_probe(run, family, batched, ref)
    if batched and device is not None and device.n_devices > 1:
        if kwargs:
            raise ValueError("keyword stream args are unsupported under "
                             "DeviceSpec sharding; pass them positionally")
        run = _shard_batch(family, run, args, device)
    return run(*args)


def stream_steps(family: str, *args, tn: int = 128, td=None,
                 state_residency: str = "vmem", buffer_depth=None,
                 force_ref: bool = False, **kwargs):
    """Time-fused V3 stream (one stream): T snapshots through ONE launch of
    the generic stream engine, dispatched by ``family``
    (``stream_fused.REGISTRY``). The family's recurrent state (node-state
    store, or EvolveGCN's evolving weights) crosses HBM exactly twice per
    stream instead of twice per step. ``td`` blocks the state feature axis
    for VMEM-oversized stores (None = fully resident); blocked and
    unblocked layouts compute identical results. ``state_residency``
    picks where the store LIVES across the stream: "vmem" (resident
    scratch) or "hbm_paged" (HBM store aliased in-place, ``(n_global,
    td)`` windows DMA-staged through a ``buffer_depth``-deep VMEM ring —
    bit-identical outputs, requires ``td``; ``buffer_depth=None`` means
    depth 2).

    Family argument lists (same order as the kernels/ref.py oracles):
      gcrn     (idx, coef, eidx, x, renumber, mask, h0, c0, wx, wh, b,
                edge_msg=None) -> (outs, hT, cT)
      stacked  (idx, coef, eidx, x, renumber, mask, h0, w_gcn, b_gcn,
                wx, wh, b, edge_msg=None) -> (outs, hT)
      evolve   (idx, coef, x, mask, live, weights, b_gcn, gru_wx, gru_wh,
                gru_b, edge_aggs=None) -> (outs, weights_T)
      tgn      (idx, coef, ts, x, renumber, mask, mem0, freq, w_in,
                wx, wh, b) -> (outs, memT)            [temporal="event":
                the T axis sequences ragged event batches, ts carries
                per-event-lane timestamps]
      static_gcn (idx, coef, x, mask, weights, b_gcn, edge_aggs=None)
                -> (outs,)                            [temporal="static":
                T must be 1; fold snapshots onto the batch axis]
    """
    return _stream_dispatch(family, False, args, kwargs, tn=tn, td=td,
                            force_ref=force_ref, residency=state_residency,
                            depth=2 if buffer_depth is None else buffer_depth)


def stream_steps_batched(family: str, *args, tn: int = 128, td=None,
                         lengths=None, device=None,
                         state_residency: str = "vmem", buffer_depth=None,
                         force_ref: bool = False, **kwargs):
    """B independent time-fused streams in ONE engine launch (the batch is
    a leading grid dimension; weights shared, one resident state per
    stream). Same family argument lists as ``stream_steps`` with a leading
    (B, ...) axis on stream arrays and per-stream state.

    ``lengths`` ((B,) ints) makes the launch RAGGED over T: stream b's
    steps past ``lengths[b]`` execute as no-ops (coef/mask zeroed,
    renumber -1, live 0 — inside the traced program, so the tail content
    of the stacked arrays is irrelevant and a length-0 row is a pure
    padding stream). ``device`` (launch/mesh.DeviceSpec) shards the
    leading B axis across devices via shard_map; streams are independent,
    so the sharded launch is bit-identical to the unsharded one."""
    return _stream_dispatch(family, True, args, kwargs, tn=tn, td=td,
                            force_ref=force_ref, lengths=lengths,
                            device=device, residency=state_residency,
                            depth=2 if buffer_depth is None else buffer_depth)
