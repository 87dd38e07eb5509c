"""Mixture-of-Experts with expert parallelism over the model axis.

Two execution paths, same math (softmax -> top-k -> renormalized combine):

dense    every expert computed on every token, combined with the top-k
         mask — exact, O(E) waste; only for smoke-test-sized configs.

ep       the production path, a shard_map over the mesh:
           1. route tokens locally (router weights replicated),
           2. bucket token copies by destination model-shard (sort +
              within-bucket position, capacity-dropped — GShard-style),
           3. all_to_all over 'model' to the expert-owning shards,
           4. locally re-bucket by expert and run the SwiGLU as one
              rectangular batched matmul per shard (MXU-friendly),
           5. all_to_all back, gate, and scatter-add into the output.
         FSDP'd expert weights are all-gathered over 'data' (bf16) inside
         the shard_map — explicit ZeRO-3.

Capacity factors make every buffer static-shape; dropped token copies lose
their expert contribution (their gate mass is renormalized over survivors
at combine). Bucket waste (cf_send * cf_local) is deliberate baseline
slack and a hillclimb lever (EXPERIMENTS §Perf).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.api import Axes, current_mesh, shard
from repro.nn.layers import ACT_DTYPE, normal_init
from repro.nn.mlp import init_mlp, mlp_block


def init_moe(key, cfg: ModelConfig, tp: int):
    d, f = cfg.d_model, cfg.d_ff
    e_pad = cfg.padded_experts(tp)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    down_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "router": normal_init(k1, (d, e_pad), 0.02),
        "w_gate": normal_init(k2, (e_pad, d, f), 0.02),
        "w_up": normal_init(k3, (e_pad, d, f), 0.02),
        "w_down": normal_init(k4, (e_pad, f, d), down_scale),
    }
    ax = {
        "router": Axes(None, None),
        "w_gate": Axes("experts", "embed_fsdp", None),
        "w_up": Axes("experts", "embed_fsdp", None),
        "w_down": Axes("experts", None, "embed_fsdp"),
    }
    if cfg.n_shared_experts:
        ps, axs = init_mlp(k5, d, cfg.n_shared_experts * f, cfg.n_layers)
        p["shared"] = ps
        ax["shared"] = axs
    return p, ax


def _route(router_w, x2, cfg: ModelConfig):
    """x2 (t, d) -> (gates (t,k) fp32 renormalized, eidx (t,k) int32)."""
    from repro.nn.layers import LOWMEM_NORM

    if LOWMEM_NORM:
        # no fp32 copy of the whole token stream: bf16 matmul with fp32
        # accumulation (router logits are tiny)
        logits = jnp.einsum("td,de->te", x2.astype(ACT_DTYPE),
                            router_w.astype(ACT_DTYPE),
                            preferred_element_type=jnp.float32)
    else:
        logits = (x2.astype(jnp.float32) @ router_w.astype(jnp.float32))
    emask = jnp.where(jnp.arange(logits.shape[-1]) < cfg.n_experts, 0.0, -1e9)
    probs = jax.nn.softmax(logits + emask, axis=-1)
    gates, eidx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eidx


# ---------------------------------------------------------------- dense ----


def _moe_dense(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    gates, eidx = _route(p["router"], x2, cfg)
    e_pad = p["w_gate"].shape[0]
    # combine weights (t, E): scatter top-k gates
    comb = (jax.nn.one_hot(eidx, e_pad, dtype=jnp.float32) * gates[..., None]).sum(axis=1)
    g = jnp.einsum("td,edf->tef", x2, p["w_gate"].astype(ACT_DTYPE))
    u = jnp.einsum("td,edf->tef", x2, p["w_up"].astype(ACT_DTYPE))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(ACT_DTYPE))
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), comb)
    return out.astype(x.dtype).reshape(b, s, d)


# ------------------------------------------------------------------- ep ----


def _bucket_by(dest: jax.Array, n_buckets: int, capacity: int):
    """Sort ids by bucket; return (order, slot, valid) where slot is the
    flat position dest*capacity + within-bucket-position (OOB when dropped)."""
    order = jnp.argsort(dest, stable=True)
    d_sorted = dest[order]
    first = jnp.searchsorted(d_sorted, jnp.arange(n_buckets), side="left")
    pos = jnp.arange(dest.shape[0]) - first[d_sorted]
    valid = pos < capacity
    slot = jnp.where(valid, d_sorted * capacity + pos, n_buckets * capacity)
    return order, slot, valid


def _ep_body(x, router_w, w_gate, w_up, w_down, *, cfg: ModelConfig, tp: int,
             e_pad: int, cap_send: int, cap_local: int, fsdp: bool):
    """Per-shard body under shard_map. x: (b_l, s_l, d) local block."""
    if fsdp:
        # explicit ZeRO-3: gather the FSDP-sharded dim (D for gate/up at
        # axis 1, D for down at axis 2) over 'data', in bf16
        w_gate = jax.lax.all_gather(w_gate.astype(ACT_DTYPE), "data", axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up.astype(ACT_DTYPE), "data", axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down.astype(ACT_DTYPE), "data", axis=2, tiled=True)
    else:
        w_gate = w_gate.astype(ACT_DTYPE)
        w_up = w_up.astype(ACT_DTYPE)
        w_down = w_down.astype(ACT_DTYPE)
    e_loc = e_pad // tp
    b_l, s_l, d = x.shape
    t = b_l * s_l
    x2 = x.reshape(t, d)
    gates, eidx = _route(router_w, x2, cfg)            # (t,k)
    k = cfg.top_k
    tok = jnp.repeat(jnp.arange(t), k)                 # (t*k,)
    e_flat = eidx.reshape(-1)
    dest = e_flat // e_loc
    order, slot, valid = _bucket_by(dest, tp, cap_send)
    # send buffers (+1 trash row dropped at gather-back)
    send_x = jnp.zeros((tp * cap_send + 1, d), ACT_DTYPE)
    send_e = jnp.zeros((tp * cap_send + 1,), jnp.int32)
    send_x = send_x.at[slot].set(x2[tok[order]].astype(ACT_DTYPE), mode="drop")
    send_e = send_e.at[slot].set(e_flat[order] % e_loc, mode="drop")
    recv_x = jax.lax.all_to_all(
        send_x[: tp * cap_send].reshape(tp, cap_send, d), "model", 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(
        send_e[: tp * cap_send].reshape(tp, cap_send), "model", 0, 0, tiled=False)
    t2 = tp * cap_send
    rx = recv_x.reshape(t2, d)
    re = recv_e.reshape(t2)
    # local re-bucket by expert -> rectangular batched matmul
    order2, slot2, valid2 = _bucket_by(re, e_loc, cap_local)
    bx = jnp.zeros((e_loc * cap_local + 1, d), ACT_DTYPE)
    bx = bx.at[slot2].set(rx[order2], mode="drop")
    bx = bx[: e_loc * cap_local].reshape(e_loc, cap_local, d)
    g = jnp.einsum("ecd,edf->ecf", bx, w_gate)
    u = jnp.einsum("ecd,edf->ecf", bx, w_up)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)
    # un-bucket locally: y back to recv slots
    y2 = jnp.zeros((t2, d), ACT_DTYPE)
    y2 = y2.at[order2].set(
        jnp.where(valid2[:, None], y.reshape(-1, d)[jnp.minimum(slot2, e_loc * cap_local - 1)], 0))
    back = jax.lax.all_to_all(y2.reshape(tp, cap_send, d), "model", 0, 0, tiled=False)
    back2 = back.reshape(t2, d)
    # gate + scatter-add into the t local tokens
    from repro.nn.layers import LOWMEM_NORM

    acc_dt = ACT_DTYPE if LOWMEM_NORM else jnp.float32
    contrib = jnp.where(valid[:, None],
                        back2[jnp.minimum(slot, t2 - 1)], 0)  # (t*k, d) in sorted order
    g_sorted = gates.reshape(-1)[order]
    out = jnp.zeros((t, d), acc_dt)
    out = out.at[tok[order]].add(contrib.astype(acc_dt)
                                 * g_sorted[:, None].astype(acc_dt))
    return out.astype(x.dtype).reshape(b_l, s_l, d)


def _mesh_axis_size(mesh, name: str) -> int:
    if mesh is None or name not in mesh.axis_names:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def moe_block(p: dict, cfg: ModelConfig, x: jax.Array, *,
              impl: str = "auto", fsdp: bool = False,
              cf_send: float = 1.25, cf_local: float = 1.25) -> jax.Array:
    """MoE sublayer (no norm/residual). x: (B, S, D)."""
    mesh = current_mesh()
    tp = _mesh_axis_size(mesh, "model")
    use_ep = (impl == "ep") or (impl == "auto" and tp > 1)
    if use_ep:
        e_pad = p["w_gate"].shape[0]
        b, s, d = x.shape
        dp = _mesh_axis_size(mesh, "data") * _mesh_axis_size(mesh, "pod")
        dp_eff = dp if b % dp == 0 else 1        # b=1 decode: replicate batch
        sp = tp if s % tp == 0 else 1
        t_local = (b // dp_eff) * (s // sp)
        cap_send = max(8, int(math.ceil(t_local * cfg.top_k * cf_send / tp)))
        cap_local = max(8, int(math.ceil(cap_send * tp * cf_local / (e_pad // tp))))
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        x_spec = P(batch_axes if dp_eff > 1 else None,
                   "model" if sp > 1 else None, None)
        w_spec = P("model", "data" if fsdp else None, None)
        body = functools.partial(
            _ep_body, cfg=cfg, tp=tp, e_pad=e_pad,
            cap_send=cap_send, cap_local=cap_local, fsdp=fsdp)
        y = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, P(None, None), w_spec, w_spec,
                      P("model", None, "data" if fsdp else None)),
            out_specs=x_spec,
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        y = _moe_dense(p, cfg, x)
    if cfg.n_shared_experts:
        y = y + mlp_block(p["shared"], x)
    return y
