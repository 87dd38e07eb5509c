"""GCRN-M2 — the integrated DGNN (DGNN-Booster V2 base model).

Graph-convolutional LSTM (eq. (3) of the paper): every gate matmul of the
LSTM is a graph convolution, with GNN1 acting on the input features and
GNN2 on the hidden state:

    gates = GC_x(x^t; G^t) + GC_h(h^{t-1}; G^t) + b
    c^t   = sigmoid(f)*c^{t-1} + sigmoid(i)*tanh(g)
    h^t   = sigmoid(o)*tanh(c^t)

Per-node recurrent state lives in a *global* store (n_global, H); the
renumber table gathers the active rows before the step and scatters the
updated rows back — the paper's renumber-table-guided DRAM fetch/writeback.

Dataflow modes:
  baseline   staged gates (four separate convolution matmuls per input).
  o1         fused gates (one concatenated matmul per input).
  v2         + intra-step GNN/RNN fusion (DGNN-Booster V2): aggregation,
             gate transform, and the LSTM elementwise update execute
             per node tile inside one Pallas kernel (kernels/dgnn_fused.py)
             — the node-queue FIFO becomes a VMEM-resident tile. Identical
             math, no HBM round-trip for the gate tensor.
  v3         + time fusion (``step_stream``): the whole snapshot stream runs
             in ONE Pallas kernel (kernels/stream_fused.py) with the h/c
             global stores living in VMEM scratch across all T steps — the
             BRAM-resident recurrent state of the paper. The store crosses
             HBM once per stream instead of once per step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.dgnn import DGNNConfig
from repro.core import gcn as G
from repro.core import rnn as R
from repro.graph.padding import PaddedSnapshot


class GCRN:
    # cell spec this model dispatches to in the stream-engine registry
    # (kernels/stream_fused.REGISTRY, via kernels/ops.stream_steps)
    stream_family = "gcrn"

    def __init__(self, cfg: DGNNConfig, impl: str = "xla", n_global: int = 4096):
        assert cfg.dgnn_type == "integrated"
        self.cfg = cfg
        self.impl = impl
        self.n_global = n_global

    def init(self, rng) -> dict:
        cfg = self.cfg
        kx, ke, ko = jax.random.split(rng, 3)
        # one LSTM param set: wx is GNN1's gate transform (input conv path),
        # wh is GNN2's (hidden conv path) — matching eq. (3)'s two GNNs.
        p = {
            "lstm": R.init_lstm(kx, cfg.in_dim, cfg.hidden),
            "head": {
                "w": jax.random.normal(ko, (cfg.hidden, cfg.out_dim), jnp.float32)
                * (1.0 / jnp.sqrt(cfg.hidden)),
                "b": jnp.zeros((cfg.out_dim,), jnp.float32),
            },
        }
        if cfg.edge_dim:
            escale = 1.0 / jnp.sqrt(cfg.edge_dim)
            p["w_edge"] = jax.random.uniform(ke, (cfg.edge_dim, cfg.in_dim),
                                             jnp.float32, -escale, escale)
        return p

    def init_state(self, params: dict, mode: str = "baseline") -> dict:
        h = jnp.zeros((self.n_global, self.cfg.hidden), jnp.float32)
        c = jnp.zeros((self.n_global, self.cfg.hidden), jnp.float32)
        return {"h": h, "c": c}

    def _gather(self, store: jax.Array, snap: PaddedSnapshot) -> jax.Array:
        safe = jnp.where(snap.renumber >= 0, snap.renumber, 0)
        return store[safe] * snap.node_mask[:, None]

    def _scatter(self, store: jax.Array, snap: PaddedSnapshot, val: jax.Array) -> jax.Array:
        idx = jnp.where(snap.renumber >= 0, snap.renumber, self.n_global)
        return store.at[idx].set(val, mode="drop")

    def step(self, params: dict, state: dict, snap: PaddedSnapshot, *,
             mode: str = "baseline") -> tuple[dict, jax.Array]:
        cfg = self.cfg
        h = self._gather(state["h"], snap)
        c = self._gather(state["c"], snap)
        x = snap.node_feat
        w_edge = params.get("w_edge")

        if mode == "v2":
            from repro.kernels import ops as kops

            edge_msg = snap.edge_feat @ w_edge if w_edge is not None else None
            h_new, c_new = kops.dgnn_fused_step(
                snap.neigh_idx, snap.neigh_coef, snap.neigh_eidx,
                x, h, c,
                params["lstm"]["wx"], params["lstm"]["wh"],
                params["lstm"]["b"], edge_msg,
            )
        else:
            fused = mode == "o1"
            # GNN1: aggregate input features; GNN2: aggregate hidden state
            if self.impl == "pallas":
                agg_x = G.propagate_ell(snap, x, w_edge)
                agg_h = G.propagate_ell(snap, h, None)
            else:
                agg_x = G.propagate_segment(snap, x, w_edge)
                agg_h = G.propagate_segment(snap, h, None)
            gates = R.lstm_gates(params["lstm"], agg_x, agg_h, fused=fused)
            h_new, c_new = R.lstm_apply_gates(gates, c)

        m = snap.node_mask[:, None]
        h_new, c_new = h_new * m, c_new * m
        out = h_new @ params["head"]["w"] + params["head"]["b"]
        new_state = {
            "h": self._scatter(state["h"], snap, h_new),
            "c": self._scatter(state["c"], snap, c_new),
        }
        return new_state, out * m

    def _stream(self, params: dict, state: dict, snaps, batched: bool,
                tn=128, td="cfg", lengths=None, device=None,
                state_residency="vmem", buffer_depth=None,
                force_ref=False):
        """Shared plumbing for the (batched) stream-engine dispatch: the
        engine is selected by ``stream_family`` from the registry; the
        D-axis block size defaults to cfg.stream_td (None = fully
        resident) unless a plan overrides it."""
        from repro.kernels import ops as kops

        td = self.cfg.stream_td if td == "cfg" else td
        w_edge = params.get("w_edge")
        edge_msg = (kops.edge_project(snaps.edge_feat, w_edge)
                    if w_edge is not None else None)
        args = (snaps.neigh_idx, snaps.neigh_coef, snaps.neigh_eidx,
                snaps.node_feat, snaps.renumber, snaps.node_mask,
                state["h"], state["c"],
                params["lstm"]["wx"], params["lstm"]["wh"],
                params["lstm"]["b"], edge_msg)
        if batched:
            outs_h, h_T, c_T = kops.stream_steps_batched(
                self.stream_family, *args, tn=tn, td=td, lengths=lengths,
                device=device,
                state_residency=state_residency, buffer_depth=buffer_depth,
                force_ref=force_ref)
        else:
            outs_h, h_T, c_T = kops.stream_steps(self.stream_family, *args,
                                                 tn=tn, td=td,
                                                 state_residency=state_residency,
                                                 buffer_depth=buffer_depth,
                                                 force_ref=force_ref)
        with jax.named_scope("head"):
            out = outs_h @ params["head"]["w"] + params["head"]["b"]
        mask = snaps.node_mask
        if lengths is not None:
            # ragged T: the masking happens inside the launch; mirror it on
            # the host-side output mask so dead-tail rows read as zero.
            live = (jnp.arange(mask.shape[1])[None, :]
                    < jnp.asarray(lengths)[:, None])
            mask = mask * live[:, :, None]
        return {"h": h_T, "c": c_T}, out * mask[..., None]

    def step_stream(self, params: dict, state: dict, snaps_T: PaddedSnapshot,
                    *, tn=128, td="cfg", state_residency="vmem",
                    buffer_depth=None) -> tuple[dict, jax.Array]:
        """V3: run a whole (T, ...) snapshot stream through the stream
        engine; h/c stay resident across steps (gather/scatter included) —
        in VMEM scratch, or HBM-paged when ``state_residency`` says so."""
        return self._stream(params, state, snaps_T, batched=False, tn=tn,
                            td=td, state_residency=state_residency,
                            buffer_depth=buffer_depth)

    def step_stream_batched(self, params: dict, state: dict,
                            snaps_BT: PaddedSnapshot, *, tn=128, td="cfg",
                            lengths=None, device=None,
                            state_residency="vmem", buffer_depth=None,
                            force_ref=False) -> tuple[dict, jax.Array]:
        """Batched V3: B independent snapshot streams — (B, T, ...) leaves,
        state leaves (B, n_global, H) — through ONE launch of the batched
        stream engine (weights shared, one VMEM-resident store per
        stream). Row b of the result is bit-close to running stream b alone
        through ``step_stream``. ``lengths`` runs the launch ragged over T;
        ``device`` (DeviceSpec) shards the batch axis; ``force_ref`` takes
        the XLA oracle path (the serve engine's degraded-mode rung)."""
        return self._stream(params, state, snaps_BT, batched=True, tn=tn,
                            td=td, lengths=lengths, device=device,
                            state_residency=state_residency,
                            buffer_depth=buffer_depth,
                            force_ref=force_ref)
