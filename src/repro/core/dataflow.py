"""Dataflow engines: sequential baseline, Pipeline-O1, V1, V2, V3.

These wrap a DGNN model's per-snapshot step into a scan over the snapshot
stream, reproducing the paper's ablation levels (Fig. 6):

  baseline     strict GNN/RNN chain per time step, staged RNN gates.
  o1           Pipeline-O1: fused RNN gate pipeline.
  v1 (o2)      Pipeline-O2 for stacked/weights-evolved DGNNs: module-level
               overlap of GNN and RNN in ADJACENT time steps. For
               weights-evolved models the overlap is expressed through the
               primed carry (see core/evolvegcn.py); for stacked models it
               is classic software pipelining with a one-step pipeline
               register (prologue/epilogue below).
  v2 (o2)      Pipeline-O2 for stacked/integrated DGNNs: intra-step fusion
               (node-queue analogue) via the fused Pallas kernel.
  v3           Time-fused stream: the whole T-step stream runs inside ONE
               launch of the generic stream-engine kernel
               (kernels/stream_fused.py) with the recurrent state living
               in VMEM scratch between snapshots — the paper's
               BRAM-resident intermediate results. Every model exposes it
               as ``step_stream`` and dispatches by its ``stream_family``
               through the engine's cell-spec REGISTRY: GCRN/stacked keep
               the (n_global, H) node-state store resident (h/c cross HBM
               once per stream instead of once per step), and EvolveGCN
               keeps its per-layer evolving weight matrices resident with
               the matrix-GRU evolution running in-kernel between
               snapshots (W_l crosses HBM twice per stream instead of
               twice per step). State stores larger than VMEM stream in
               (n_global, td) column tiles via the engine's D grid axis
               (cfg.stream_td; see docs/stream_engine.md).

Ablation summary (what each level removes from the critical path):

  level     | scope of fusion       | recurrent-state HBM traffic
  baseline  | none                  | 2T transfers / stream (in + out each step)
  o1        | RNN gate pipeline     | 2T
  v1        | adjacent-step overlap | 2T (pipeline register added)
  v2        | intra-step GNN+RNN    | 2T (gate tensor stays in VMEM)
  v3        | whole stream          | 2  (state resident across all T steps)

(for EvolveGCN the "recurrent state" column reads on the evolving weight
matrices instead of the node-state store — same 2T -> 2 reduction.)

All modes compute IDENTICAL outputs for the same params/stream — that is
the correctness contract the paper verifies against PyTorch, and what our
tests assert. The difference is the critical path / fusion structure, which
shows up in the lowered HLO (benchmarks/fig6_ablation.py measures it).

Snapshot streams are pytrees with a leading T axis (same padding bucket);
multi-stream batching adds a B axis (``run_plan_batched``): v3 runs the
whole (B, T) batch in ONE batched stream-kernel launch — optionally
RAGGED over T (per-stream lengths) and sharded over devices (DeviceSpec),
both carried by the plan — while other levels vmap the per-stream scan.

Dispatch is by typed StreamPlan (repro.api): ``run_plan`` /
``run_plan_batched`` execute a validated plan; the historical mode-string
entry points ``run_stream`` / ``run_batched`` survive as deprecated shims
that build the equivalent plan.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dgnn import DGNNConfig
from repro.core.evolvegcn import EvolveGCN
from repro.core.gcn import StaticGCN
from repro.core.gcrn import GCRN
from repro.core.stacked import StackedDGNN
from repro.core.tgn import TGNModel

Model = Any  # EvolveGCN | GCRN | StackedDGNN | StaticGCN | TGNModel


def build_model(cfg: DGNNConfig, impl: str = "xla", n_global: int = 4096) -> Model:
    if cfg.dgnn_type == "weights_evolved":
        return EvolveGCN(cfg, impl=impl)
    if cfg.dgnn_type == "integrated":
        return GCRN(cfg, impl=impl, n_global=n_global)
    if cfg.dgnn_type == "stacked":
        return StackedDGNN(cfg, impl=impl, n_global=n_global)
    if cfg.dgnn_type == "static":
        return StaticGCN(cfg, impl=impl, n_global=n_global)
    if cfg.dgnn_type == "event_memory":
        return TGNModel(cfg, impl=impl, n_global=n_global)
    raise ValueError(cfg.dgnn_type)


def _scan_steps(model: Model, params, state0, snaps_T, mode: str):
    def body(state, snap):
        new_state, out = model.step(params, state, snap, mode=mode)
        return new_state, out

    return jax.lax.scan(body, state0, snaps_T)


def _run_stacked_v1(model: StackedDGNN, params, state0, snaps_T):
    """Software-pipelined stacked DGNN: GCN(G^t) overlaps GRU(X^{t-1}).

    Pipeline register: (X^{t-1}, snap^{t-1}). Prologue computes X^0;
    body t>=1 computes X^t (GNN) and consumes X^{t-1} (RNN) — two
    independent subgraphs inside one scan iteration. Epilogue drains the
    last X. Outputs are identical to the sequential schedule.
    """
    first = jax.tree.map(lambda a: a[0], snaps_T)
    rest = jax.tree.map(lambda a: a[1:], snaps_T)
    x0 = model.gnn(params, first)  # prologue

    def body(carry, snap):
        state, x_prev, snap_prev = carry
        # independent: GNN on this step's graph, RNN on last step's output
        x_t = model.gnn(params, snap)
        new_state, h = model.rnn(params, state, snap_prev, x_prev, fused=True)
        return (new_state, x_t, snap), h

    (state, x_last, snap_last), outs = jax.lax.scan(body, (state0, x0, first), rest)
    state, h_last = model.rnn(params, state, snap_last, x_last, fused=True)  # epilogue
    outs = jnp.concatenate([outs, h_last[None]], axis=0)
    return state, outs


def run_plan(model: Model, params, state0, snaps_T, plan):
    """Execute a typed StreamPlan (repro.api) on one (T, ...) stream.

    The plan's ``level`` selects the dataflow engine and its ``tn``/``td``
    the engine tiling; validity was established when the plan was built,
    so there is no mode-string dispatch left to go wrong here. Returns
    (final_state, outputs (T, n_pad, out_dim)).
    """
    if plan.lengths is not None:
        raise ValueError("plan carries ragged lengths — a batched-launch "
                         "capability; use run_plan_batched")
    if plan.level == "v1" and isinstance(model, StackedDGNN):
        return _run_stacked_v1(model, params, state0, snaps_T)
    if plan.level == "v3":
        # every family has a time-fused stream engine: node-state-resident
        # for GCRN/stacked, weights-resident for EvolveGCN.
        return model.step_stream(params, state0, snaps_T, tn=plan.tn,
                                 td=plan.td,
                                 state_residency=plan.state_residency,
                                 buffer_depth=plan.buffer_depth)
    return _scan_steps(model, params, state0, snaps_T, plan.level)


def run_plan_batched(model: Model, params, states0, snaps_BT, plan,
                     lengths=None):
    """Execute a StreamPlan on B independent streams: snaps arrays are
    (B, T, ...), states (B, ...). Params are shared across streams;
    recurrent state is not. This is the production throughput axis
    (DESIGN §4).

    level="v3" dispatches to the model's ``step_stream_batched`` — the
    batch axis becomes a leading grid dimension of ONE time-fused kernel
    launch (kernels/stream_fused.py), so every stream's recurrent state
    still crosses HBM exactly twice — carrying the plan's two
    batch-capabilities: ``lengths`` (ragged per-stream T, masked in-launch)
    and ``device`` (DeviceSpec sharding of the B grid axis). Other levels
    vmap the per-stream engine (equal T only)."""
    # static families carry an EMPTY state pytree — the batch size then
    # comes from the snapshot leaves instead.
    leaves = jax.tree.leaves(states0) or jax.tree.leaves(snaps_BT)
    B = leaves[0].shape[0]
    if B != plan.batch:
        raise ValueError(f"plan.batch={plan.batch} but the state batch "
                         f"is {B}")
    lengths = plan.lengths if lengths is None else lengths
    if plan.level == "v3":
        lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
        return model.step_stream_batched(params, states0, snaps_BT,
                                         tn=plan.tn, td=plan.td,
                                         lengths=lens, device=plan.device,
                                         state_residency=plan.state_residency,
                                         buffer_depth=plan.buffer_depth)
    if lengths is not None:
        raise ValueError("ragged lengths need the stream engine "
                         f"(level='v3'); level={plan.level!r}")
    fn = lambda st, sT: run_plan(model, params, st, sT, plan)
    return jax.vmap(fn)(states0, snaps_BT)


# ------------------------------------------------- deprecated shims ----
# The historical mode-string surface. New code builds a typed plan
# (repro.api.plan / BoosterSession); these shims construct the equivalent
# plan and execute it, so their outputs are bit-identical to the plan
# path by construction.

def _shim_plan(model: Model, mode: str, batch: int = 1):
    from repro import api

    return api.plan(family=model.stream_family, level=mode,
                    td=model.cfg.stream_td, batch=batch)


def run_stream(model: Model, params, state0, snaps_T, mode: str = "baseline"):
    """Deprecated: build a repro.api.StreamPlan instead (this shim does,
    then executes it). Returns (final_state, outputs (T, n_pad, out_dim))."""
    import warnings

    warnings.warn(
        "core.dataflow.run_stream is deprecated: build a typed plan "
        "(repro.api.plan / BoosterSession.run) instead",
        DeprecationWarning, stacklevel=2)
    return run_plan(model, params, state0, snaps_T, _shim_plan(model, mode))


def run_batched(model: Model, params, states0, snaps_TB, mode: str = "baseline"):
    """Deprecated: build a repro.api.StreamPlan instead (this shim does,
    then executes it). Batched streams in the historical (T, B, ...)
    layout; see ``run_plan_batched`` for the (B, T, ...) plan executor."""
    import warnings

    warnings.warn(
        "core.dataflow.run_batched is deprecated: build a typed plan "
        "(repro.api.plan / BoosterSession.run_batched) instead",
        DeprecationWarning, stacklevel=2)
    snaps_BT = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), snaps_TB)
    leaves = jax.tree.leaves(states0) or jax.tree.leaves(snaps_BT)
    B = leaves[0].shape[0]
    state, outs_BT = run_plan_batched(model, params, states0, snaps_BT,
                                      _shim_plan(model, mode, batch=B))
    return state, jnp.swapaxes(outs_BT, 0, 1)


def init_states_batched(model: Model, params, n_streams: int,
                        mode: str = "baseline"):
    """Stack ``n_streams`` independent recurrent states along a leading B
    axis (each stream starts from the model's fresh state)."""
    s0 = model.init_state(params, mode=mode)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_streams,) + a.shape), s0)


def _consecutive_rows(xs: tuple):
    """``base[i:i + len(xs)]``, a view, when ``xs`` are the rows i, i+1, ...
    of one C-contiguous array ``base`` in order; else None."""
    base = getattr(xs[0], "base", None)
    if (not isinstance(base, np.ndarray) or base.ndim == 0 or base.size == 0
            or not base.flags.c_contiguous):
        return None
    shape, strides, row = base.shape[1:], base.strides[1:], base.strides[0]
    p0 = xs[0].ctypes.data
    i, off = divmod(p0 - base.ctypes.data, row)
    if off or i + len(xs) > base.shape[0]:
        return None
    for k, x in enumerate(xs):
        if (getattr(x, "base", None) is not base or x.shape != shape
                or x.strides != strides or x.dtype != base.dtype
                or x.ctypes.data != p0 + k * row):
            return None
    return base[i:i + len(xs)]


def stack_time(padded_snaps: list) -> Any:
    """Stack per-step PaddedSnapshots (same bucket) along a leading T axis.

    A leaf whose steps are consecutive rows of one array, in order (the
    serve producers pad each chunk into the rows of a
    ``graph.padding.chunk_slab``), comes back as a view of those rows with
    no copy; any other leaf is copied by ``np.stack``."""
    def leaf(*xs):
        rows = _consecutive_rows(xs)
        return np.stack(xs, axis=0) if rows is None else rows

    return jax.tree.map(leaf, *padded_snaps)
