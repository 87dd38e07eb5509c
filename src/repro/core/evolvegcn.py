"""EvolveGCN-O — the weights-evolved DGNN (DGNN-Booster V1 base model).

Per GCN layer l, a matrix-GRU evolves the layer weight:
    W_l^t = GRU(W_l^{t-1})            (temporal encoding)
    H^t   = GCN(W^t, G^t)             (spatial encoding)

Dataflow modes (see core/dataflow.py for the scan wrappers):
  baseline   strict chain inside one step: evolve -> GCN (paper Fig. 3).
  o1         + fused-gate GRU (Pipeline-O1).
  v1         + module overlap (Pipeline-O2 / DGNN-Booster V1): the state
             carries *already evolved* weights W^t, so GCN(W^t, G^t) and
             GRU(W^t) -> W^{t+1} are dataflow-independent inside the scan
             body — the ping-pong-buffer schedule. Outputs are identical
             to baseline (the state is primed by one evolution).
  v3         time fusion (``step_stream``): the whole snapshot stream runs
             in ONE weights-resident Pallas kernel
             (kernels/stream_fused.py): the per-layer evolving weights
             W_l^t live in VMEM scratch across all T steps, the
             matrix-GRU evolution runs in-kernel between snapshots, and
             the multi-layer GCN consumes the resident weights — each W_l
             crosses HBM twice per stream (primed load + evolved drain)
             instead of twice per step. Same primed-carry convention as
             v1, so v1 and v3 states are interchangeable at chunk
             boundaries (the serve engine relies on this).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.dgnn import DGNNConfig
from repro.core import gcn as G
from repro.core import rnn as R
from repro.graph.padding import PaddedSnapshot


def layer_dims(cfg: DGNNConfig) -> list[tuple[int, int]]:
    dims = []
    din = cfg.in_dim
    for l in range(cfg.n_gnn_layers):
        dout = cfg.out_dim if l == cfg.n_gnn_layers - 1 else cfg.hidden
        dims.append((din, dout))
        din = dout
    return dims


class EvolveGCN:
    # cell spec this model dispatches to in the stream-engine registry
    stream_family = "evolve"

    def __init__(self, cfg: DGNNConfig, impl: str = "xla"):
        assert cfg.dgnn_type == "weights_evolved"
        self.cfg = cfg
        self.impl = impl

    def init(self, rng) -> dict:
        dims = layer_dims(self.cfg)
        keys = jax.random.split(rng, 2 * len(dims))
        layers, grus = [], []
        for l, (din, dout) in enumerate(dims):
            layers.append(G.init_gcn_layer(keys[2 * l], din, dout, self.cfg.edge_dim))
            grus.append(R.init_gru(keys[2 * l + 1], din, din))
        return {"gcn": layers, "gru": grus}

    def init_state(self, params: dict, mode: str = "baseline") -> dict:
        """Recurrent state: the evolving weight matrices (per stream).

        v1 primes the pipeline by evolving once, so that inside the scan
        body the GCN consumes W^t while the GRU produces W^{t+1}; outputs
        then match baseline exactly. v3 (the weights-resident stream
        kernel) uses the SAME primed convention: the kernel consumes the
        incoming weights at its first snapshot without evolving them and
        evolves at the END of every live step — priming once here and
        evolving in-kernel would otherwise double-evolve (the regression
        the differential harness pins).
        """
        weights = [p["w"] for p in params["gcn"]]
        if mode in ("v1", "v3"):
            weights = [
                R.matrix_gru(g, w, fused=True)
                for g, w in zip(params["gru"], weights)
            ]
        return {"weights": weights}

    def step(self, params: dict, state: dict, snap: PaddedSnapshot, *,
             mode: str = "baseline") -> tuple[dict, jax.Array]:
        # mode="v3" streams route through step_stream (the weights-resident
        # kernel); per-STEP v3 semantics equal the v1 overlapped schedule
        # (same primed carry), so a v3 state stepped here stays exchangeable
        # with the stream kernel's.
        fused = mode in ("o1", "v1", "v3")
        # an EMPTY snapshot is a no-op in every engine: outputs are masked
        # to zero and the weights do not evolve — the same contract the
        # stream kernel's live flag enforces, so all modes stay identical
        # even on streams containing empty (or no-op padding) snapshots.
        live = snap.n_nodes > 0
        if mode in ("v1", "v3"):
            # DGNN-Booster V1: GCN and GRU are independent given the carry.
            w_now = state["weights"]
            out = G.gcn_forward_weights(params["gcn"], w_now, snap,
                                        snap.node_feat, impl=self.impl)
            w_next = [jnp.where(live, R.matrix_gru(g, w, fused=True), w)
                      for g, w in zip(params["gru"], w_now)]
            return {"weights": w_next}, out
        # baseline / o1: evolve THEN apply — the sequential critical path.
        w_now = [jnp.where(live, R.matrix_gru(g, w, fused=fused), w)
                 for g, w in zip(params["gru"], state["weights"])]
        out = G.gcn_forward_weights(params["gcn"], w_now, snap,
                                    snap.node_feat, impl=self.impl)
        return {"weights": w_now}, out

    def _edge_aggs(self, params: dict, snaps: PaddedSnapshot):
        """Per-layer pre-aggregated edge-message term for the stream
        kernel: sum_k coef[v,k] * (edge_feat @ w_edge_l)[eidx[v,k]], shape
        (..., n, din_l) with any leading (T,) / (B, T) axes. The edge
        contribution is additive in the ELL aggregation, so it factors out
        of the kernel (which then only gathers node activations)."""
        from repro.kernels import ops as kops

        if not self.cfg.edge_dim:
            return None
        return [kops.edge_aggregate(
                    snaps.neigh_coef, snaps.neigh_eidx,
                    kops.edge_project(snaps.edge_feat, p["w_edge"]))
                for p in params["gcn"]]

    def _run_stream_kernel(self, params: dict, state: dict,
                           snaps: PaddedSnapshot, batched: bool,
                           tn=128, td="cfg", lengths=None, device=None,
                           state_residency="vmem", buffer_depth=None,
                           force_ref=False) -> tuple[dict, jax.Array]:
        """Shared plumbing for the (batched) stream-engine dispatch:
        live flags (n_nodes > 0 — no-op padding snapshots must not evolve
        the weights), per-layer param lists, edge aggregates."""
        from repro.kernels import ops as kops

        td = self.cfg.stream_td if td == "cfg" else td
        live = (snaps.n_nodes > 0).astype(jnp.int32)
        args = (snaps.neigh_idx, snaps.neigh_coef, snaps.node_feat,
                snaps.node_mask, live, list(state["weights"]),
                [p["b"] for p in params["gcn"]],
                [g["wx"] for g in params["gru"]],
                [g["wh"] for g in params["gru"]],
                [g["b"] for g in params["gru"]],
                self._edge_aggs(params, snaps))
        if batched:
            outs, wT = kops.stream_steps_batched(
                self.stream_family, *args, tn=tn, td=td, lengths=lengths,
                device=device,
                state_residency=state_residency, buffer_depth=buffer_depth,
                force_ref=force_ref)
        else:
            outs, wT = kops.stream_steps(self.stream_family, *args,
                                         tn=tn, td=td,
                                         state_residency=state_residency,
                                         buffer_depth=buffer_depth,
                                         force_ref=force_ref)
        return {"weights": list(wT)}, outs

    def step_stream(self, params: dict, state: dict, snaps_T: PaddedSnapshot,
                    *, tn=128, td="cfg", state_residency="vmem",
                    buffer_depth=None) -> tuple[dict, jax.Array]:
        """V3: run a whole (T, ...) snapshot stream through the
        weights-resident kernel; the evolving W_l stay in VMEM across
        steps and the matrix-GRU evolution runs in-kernel between
        snapshots."""
        return self._run_stream_kernel(params, state, snaps_T, batched=False,
                                       tn=tn, td=td,
                                       state_residency=state_residency,
                                       buffer_depth=buffer_depth)

    def step_stream_batched(self, params: dict, state: dict,
                            snaps_BT: PaddedSnapshot, *, tn=128, td="cfg",
                            lengths=None, device=None,
                            state_residency="vmem", buffer_depth=None,
                            force_ref=False) -> tuple[dict, jax.Array]:
        """Batched V3: B independent streams — (B, T, ...) leaves, weight
        state leaves (B, din_l, dout_l) — through ONE launch of the
        batched weights-resident kernel (GRU params shared, one resident
        weight set per stream). Row b of the result is bit-close to
        running stream b alone through ``step_stream``. ``lengths`` runs
        the launch ragged over T; ``device`` (DeviceSpec) shards the
        batch axis; ``force_ref`` takes the XLA oracle path (the serve
        engine's degraded-mode rung)."""
        return self._run_stream_kernel(params, state, snaps_BT, batched=True,
                                       tn=tn, td=td, lengths=lengths,
                                       device=device,
                                       state_residency=state_residency,
                                       buffer_depth=buffer_depth,
                                       force_ref=force_ref)
