"""GCN spatial encoder with message passing + edge embeddings.

The paper implements its GNN with the GenGNN message-passing mechanism and
highlights edge-embedding support. We follow the paper's stage split:

  MP (message passing): for each node v, agg[v] = sum over in-edges (u->v)
      of coef(u,v) * (x[u] + proj(edge_feat)), with coef the symmetric GCN
      normalization (precomputed host-side during renumbering);
  NT (node transform): h'[v] = act(agg[v] @ W + b).

Two device paths compute the same math:
  impl="xla"    edge-parallel gather + segment_sum (reference, used by the
                pjit production path — XLA fuses it well on TPU),
  impl="pallas" the ELL SpMM Pallas kernel (kernels/csr_spmm.py), the V2
                building block with VMEM-resident node features.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.graph.padding import PaddedSnapshot


def init_gcn_layer(rng, din: int, dout: int, edge_dim: int) -> dict:
    kw, ke = jax.random.split(rng)
    scale = 1.0 / jnp.sqrt(din)
    p = {
        "w": jax.random.uniform(kw, (din, dout), jnp.float32, -scale, scale),
        "b": jnp.zeros((dout,), jnp.float32),
    }
    if edge_dim:
        escale = 1.0 / jnp.sqrt(edge_dim)
        p["w_edge"] = jax.random.uniform(ke, (edge_dim, din), jnp.float32, -escale, escale)
    return p


def propagate_segment(snap: PaddedSnapshot, x: jax.Array, w_edge=None) -> jax.Array:
    """MP stage, edge-parallel reference: (e_pad) gathers + segment_sum."""
    msgs = x[snap.src]
    if w_edge is not None:
        msgs = msgs + snap.edge_feat @ w_edge
    msgs = msgs * snap.coef[:, None]
    return jax.ops.segment_sum(msgs, snap.dst, num_segments=x.shape[0])


def propagate_ell(snap: PaddedSnapshot, x: jax.Array, w_edge=None) -> jax.Array:
    """MP stage via the ELL layout (same layout the Pallas kernel consumes)."""
    from repro.kernels import ops as kops

    edge_msg = snap.edge_feat @ w_edge if w_edge is not None else None
    return kops.ell_spmm(snap.neigh_idx, snap.neigh_coef, snap.neigh_eidx, x, edge_msg)


def gcn_layer(params: dict, snap: PaddedSnapshot, x: jax.Array, *,
              act=jax.nn.relu, impl: str = "xla") -> jax.Array:
    """One GCN layer: MP then NT (the paper's stage order)."""
    w_edge = params.get("w_edge")
    if impl == "pallas":
        agg = propagate_ell(snap, x, w_edge)
    else:
        agg = propagate_segment(snap, x, w_edge)
    h = agg @ params["w"] + params["b"]
    if act is not None:
        h = act(h)
    return h * snap.node_mask[:, None]


def gcn_forward(layers: list[dict], snap: PaddedSnapshot, x: jax.Array, *,
                impl: str = "xla") -> jax.Array:
    """Multi-layer GCN; last layer linear (standard GCN head)."""
    for i, p in enumerate(layers):
        last = i == len(layers) - 1
        x = gcn_layer(p, snap, x, act=None if last else jax.nn.relu, impl=impl)
    return x


def gcn_forward_weights(layers: list[dict], weights: list[jax.Array],
                        snap: PaddedSnapshot, x: jax.Array, *,
                        impl: str = "xla") -> jax.Array:
    """GCN forward with externally supplied weight matrices (EvolveGCN:
    the evolved ``weights`` replace params['w'] layer by layer)."""
    for i, (p, w) in enumerate(zip(layers, weights)):
        last = i == len(layers) - 1
        q = dict(p, w=w)
        x = gcn_layer(q, snap, x, act=None if last else jax.nn.relu, impl=impl)
    return x


class StaticGCN:
    """The "static" temporal contract's model: a plain multi-layer GCN,
    no recurrence, zero state (GenGNN-style non-temporal traffic).

    A "stream" of static snapshots is just a batch of independent graphs:
    ``step_stream`` folds the T axis onto the engine's batch axis (every
    slot T=1 — the static cell spec rejects anything else) and
    ``step_stream_batched`` folds (B, T) onto (B*T, 1), converting the
    plan's ragged ``lengths`` into per-slot 0/1 liveness. Every dataflow
    level computes the identical forward — the point of the family is the
    serve engine's EXPRESS lane: stateless chunks co-batch into one
    launch with no checkpoint/rollback overhead (serve/engine.py).
    """

    # cell spec this model dispatches to in the stream-engine registry
    stream_family = "static_gcn"

    def __init__(self, cfg, impl: str = "xla", n_global: int = 4096):
        assert cfg.dgnn_type == "static"
        self.cfg = cfg
        self.impl = impl
        self.n_global = n_global

    def init(self, rng) -> dict:
        cfg = self.cfg
        keys = jax.random.split(rng, cfg.n_gnn_layers)
        layers = []
        din = cfg.in_dim
        for l in range(cfg.n_gnn_layers):
            dout = cfg.out_dim if l == cfg.n_gnn_layers - 1 else cfg.hidden
            layers.append(init_gcn_layer(keys[l], din, dout,
                                         cfg.edge_dim if l == 0 else 0))
            din = dout
        return {"gcn": layers}

    def init_state(self, params: dict, mode: str = "baseline") -> dict:
        return {}  # stateless: the engine skips init/copy-forward/drain

    def step(self, params: dict, state: dict, snap: PaddedSnapshot, *,
             mode: str = "baseline") -> tuple[dict, jax.Array]:
        return state, gcn_forward(params["gcn"], snap, snap.node_feat,
                                  impl=self.impl)

    # ------------------------------------------------- stream engine ----

    def _edge_aggs(self, params: dict, snaps):
        """Per-layer pre-aggregated edge-message term (additive in the
        ELL aggregation, so it factors out of the kernel); zero for
        layers without edge weights (only layer 0 projects edges)."""
        from repro.kernels import ops as kops

        if params["gcn"][0].get("w_edge") is None:
            return None
        lead_n = snaps.neigh_eidx.shape[:-1]
        return [jnp.zeros((*lead_n, p["w"].shape[0]), jnp.float32)
                if p.get("w_edge") is None
                else kops.edge_aggregate(
                    snaps.neigh_coef, snaps.neigh_eidx,
                    kops.edge_project(snaps.edge_feat, p["w_edge"]))
                for p in params["gcn"]]

    @staticmethod
    def _check_residency(state_residency, buffer_depth):
        # accepted for interface parity with the stateful families, but a
        # static family has no recurrent store to page
        if state_residency != "vmem" or buffer_depth is not None:
            raise ValueError(
                "state_residency='hbm_paged' is undefined for static "
                "family 'static_gcn': zero StateDefs — there is no "
                "recurrent store to page")

    def _stream_args(self, params: dict, snaps):
        return (snaps.neigh_idx, snaps.neigh_coef, snaps.node_feat,
                snaps.node_mask, [p["w"] for p in params["gcn"]],
                [p["b"] for p in params["gcn"]],
                self._edge_aggs(params, snaps))

    def step_stream(self, params: dict, state: dict,
                    snaps_T: PaddedSnapshot, *, tn=128, td="cfg",
                    state_residency="vmem", buffer_depth=None
                    ) -> tuple[dict, jax.Array]:
        """V3: T independent snapshots fold onto the engine's batch axis
        (one launch, T batch slots of a single T=1 step each)."""
        from repro.kernels import ops as kops

        self._check_residency(state_residency, buffer_depth)
        td = self.cfg.stream_td if td == "cfg" else td
        snaps_B1 = jax.tree.map(lambda a: jnp.asarray(a)[:, None], snaps_T)
        (outs,) = kops.stream_steps_batched(
            self.stream_family, *self._stream_args(params, snaps_B1),
            tn=tn, td=td)
        return state, outs[:, 0]

    def step_stream_batched(self, params: dict, state: dict,
                            snaps_BT: PaddedSnapshot, *, tn=128, td="cfg",
                            lengths=None, device=None,
                            state_residency="vmem", buffer_depth=None,
                            force_ref=False) -> tuple[dict, jax.Array]:
        """Batched V3: (B, T) independent snapshots fold onto (B*T, 1);
        ragged ``lengths`` (per-stream T) become per-slot 0/1 liveness.
        ``state`` passes through untouched (empty per slot)."""
        from repro.kernels import ops as kops

        self._check_residency(state_residency, buffer_depth)
        td = self.cfg.stream_td if td == "cfg" else td
        leaf = jax.tree.leaves(snaps_BT)[0]
        B, T = leaf.shape[0], leaf.shape[1]
        folded = jax.tree.map(
            lambda a: jnp.asarray(a).reshape((B * T, 1) + a.shape[2:]),
            snaps_BT)
        slot_lens = None
        if lengths is not None:
            lens = jnp.asarray(lengths, jnp.int32)
            t_axis = jnp.arange(T, dtype=jnp.int32)
            slot_lens = (t_axis[None, :] < lens[:, None]).astype(
                jnp.int32).reshape(B * T)
        (outs,) = kops.stream_steps_batched(
            self.stream_family, *self._stream_args(params, folded),
            tn=tn, td=td, lengths=slot_lens, device=device,
            force_ref=force_ref)
        return state, outs.reshape((B, T) + outs.shape[2:])
