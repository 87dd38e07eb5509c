"""Stacked DGNN (GCRN-M1 / WD-GCN style) — GNN feeds a per-node GRU.

The third discrete-time DGNN type of Table I, included so the framework
covers the whole taxonomy (both V1 and V2 apply to it):

    X^t = GCN(G^t)                 (independent across time)
    h^t = GRU(X^t, h^{t-1})        (chained across time, per node)

Dataflow modes:
  baseline   GCN then GRU, chained inside every step.
  o1         + fused-gate GRU.
  v1         software-pipelined: the scan body computes GCN(G^{t}) and
             GRU(X^{t-1}) concurrently (X carried in the state, one-step
             prologue/epilogue handled in core/dataflow.py).
  v2         intra-step fusion via the Pallas fused kernel (GRU variant).
  v3         time fusion (``step_stream``): last GCN layer + GRU for the
             whole stream in one Pallas kernel, the global h store
             VMEM-resident across all T steps (kernels/stream_fused.py).
             Earlier GCN layers are time-independent and run vmapped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.dgnn import DGNNConfig
from repro.core import gcn as G
from repro.core import rnn as R
from repro.graph.padding import PaddedSnapshot


class StackedDGNN:
    # cell spec this model dispatches to in the stream-engine registry
    stream_family = "stacked"

    def __init__(self, cfg: DGNNConfig, impl: str = "xla", n_global: int = 4096):
        assert cfg.dgnn_type == "stacked"
        self.cfg = cfg
        self.impl = impl
        self.n_global = n_global

    def init(self, rng) -> dict:
        cfg = self.cfg
        keys = jax.random.split(rng, cfg.n_gnn_layers + 1)
        layers = []
        din = cfg.in_dim
        for l in range(cfg.n_gnn_layers):
            layers.append(G.init_gcn_layer(keys[l], din, cfg.hidden, cfg.edge_dim if l == 0 else 0))
            din = cfg.hidden
        return {"gcn": layers, "gru": R.init_gru(keys[-1], cfg.hidden, cfg.hidden)}

    def init_state(self, params: dict, mode: str = "baseline") -> dict:
        # v1's pipeline register (X^{t-1}) is managed by core/dataflow.py,
        # not stored here — the recurrent state is just the global h store.
        h = jnp.zeros((self.n_global, self.cfg.hidden), jnp.float32)
        return {"h": h}

    def _gather(self, store, snap):
        safe = jnp.where(snap.renumber >= 0, snap.renumber, 0)
        return store[safe] * snap.node_mask[:, None]

    def _scatter(self, store, snap, val):
        idx = jnp.where(snap.renumber >= 0, snap.renumber, self.n_global)
        return store.at[idx].set(val, mode="drop")

    def gnn(self, params: dict, snap: PaddedSnapshot) -> jax.Array:
        return G.gcn_forward(params["gcn"], snap, snap.node_feat, impl=self.impl)

    def rnn(self, params: dict, state: dict, snap: PaddedSnapshot, x: jax.Array,
            *, fused: bool) -> tuple[dict, jax.Array]:
        h = self._gather(state["h"], snap)
        h_new = R.gru_cell(params["gru"], x, h, fused=fused) * snap.node_mask[:, None]
        return {"h": self._scatter(state["h"], snap, h_new)}, h_new

    def step(self, params: dict, state: dict, snap: PaddedSnapshot, *,
             mode: str = "baseline") -> tuple[dict, jax.Array]:
        if mode == "v2":
            from repro.kernels import ops as kops

            w_edge = params["gcn"][0].get("w_edge")
            # single-layer GNN fast path feeds the fused kernel; deeper GNNs
            # stream their last layer through it.
            x = snap.node_feat
            for p in params["gcn"][:-1]:
                x = G.gcn_layer(p, snap, x, impl=self.impl)
            p_last = params["gcn"][-1]
            h = self._gather(state["h"], snap)
            edge_msg = (snap.edge_feat @ w_edge) if (w_edge is not None and len(params["gcn"]) == 1) else None
            h_new = kops.stacked_fused_step(
                snap.neigh_idx, snap.neigh_coef, snap.neigh_eidx,
                x, h,
                p_last["w"], p_last["b"],
                params["gru"]["wx"], params["gru"]["wh"], params["gru"]["b"],
                edge_msg,
            )
            h_new = h_new * snap.node_mask[:, None]
            return {"h": self._scatter(state["h"], snap, h_new)}, h_new
        fused = mode in ("o1", "v1")
        x = self.gnn(params, snap)
        new_state, h_new = self.rnn(params, state, snap, x, fused=fused)
        return new_state, h_new

    def _stream(self, params: dict, state: dict, snaps, batched: bool,
                tn=128, td="cfg", lengths=None, device=None,
                state_residency="vmem", buffer_depth=None,
                force_ref=False):
        """Shared plumbing for the (batched) stream-engine dispatch.

        GCN layers before the last have no temporal dependence, so they
        run vmapped outside the kernel (doubly vmapped when batched: time-
        AND stream-independent); the last layer + GRU + store
        gather/scatter execute inside the engine with h resident in
        VMEM."""
        from repro.kernels import ops as kops

        td = self.cfg.stream_td if td == "cfg" else td
        gcn_vmap = jax.vmap if not batched else (
            lambda f: jax.vmap(jax.vmap(f)))
        x = snaps.node_feat
        for p in params["gcn"][:-1]:
            x = gcn_vmap(
                lambda s, xx, p=p: G.gcn_layer(p, s, xx, impl=self.impl)
            )(snaps, x)
        p_last = params["gcn"][-1]
        w_edge = params["gcn"][0].get("w_edge")
        edge_msg = (kops.edge_project(snaps.edge_feat, w_edge)
                    if (w_edge is not None and len(params["gcn"]) == 1)
                    else None)
        args = (snaps.neigh_idx, snaps.neigh_coef, snaps.neigh_eidx,
                x, snaps.renumber, snaps.node_mask, state["h"],
                p_last["w"], p_last["b"],
                params["gru"]["wx"], params["gru"]["wh"], params["gru"]["b"],
                edge_msg)
        if batched:
            outs_h, h_T = kops.stream_steps_batched(
                self.stream_family, *args, tn=tn, td=td, lengths=lengths,
                device=device,
                state_residency=state_residency, buffer_depth=buffer_depth,
                force_ref=force_ref)
        else:
            outs_h, h_T = kops.stream_steps(self.stream_family, *args,
                                            tn=tn, td=td,
                                            state_residency=state_residency,
                                            buffer_depth=buffer_depth,
                                            force_ref=force_ref)
        return {"h": h_T}, outs_h

    def step_stream(self, params: dict, state: dict, snaps_T: PaddedSnapshot,
                    *, tn=128, td="cfg", state_residency="vmem",
                    buffer_depth=None) -> tuple[dict, jax.Array]:
        """V3: whole (T, ...) stream through the stream engine."""
        return self._stream(params, state, snaps_T, batched=False, tn=tn,
                            td=td, state_residency=state_residency,
                            buffer_depth=buffer_depth)

    def step_stream_batched(self, params: dict, state: dict,
                            snaps_BT: PaddedSnapshot, *, tn=128, td="cfg",
                            lengths=None, device=None,
                            state_residency="vmem", buffer_depth=None,
                            force_ref=False) -> tuple[dict, jax.Array]:
        """Batched V3: B independent streams — (B, T, ...) leaves, state
        leaves (B, n_global, H) — through one launch of the batched stream
        engine. ``lengths`` runs the launch ragged over T; ``device``
        (DeviceSpec) shards the batch axis; ``force_ref`` takes the XLA
        oracle path (the serve engine's degraded-mode rung)."""
        return self._stream(params, state, snaps_BT, batched=True, tn=tn,
                            td=td, lengths=lengths, device=device,
                            state_residency=state_residency,
                            buffer_depth=buffer_depth,
                            force_ref=force_ref)
