"""GQA attention: grouped einsum, q-chunked long-sequence path, KV-cache
decode with sequence-sharded caches for long contexts.

Never materializes the KV-head repeat: queries reshape to
(B, S, Hkv, group, hd) and scores are computed per KV head group.

Three paths:
  full      plain softmax attention (S small: train_4k, smoke tests)
  chunked   lax.map over query chunks, each attending the full (masked) KV —
            O(S * chunk) live memory; the baseline for prefill_32k. Causal
            waste (upper-triangle compute) is visible in the roofline and is
            a hillclimb lever (see kernels/flash_attention.py).
  decode    one-token query against a cache laid out (B, Skv, Hkv, hd);
            softmax reductions over a sharded Skv are handled by GSPMD
            (flash-decoding-style partial combines) when the cache is
            sequence-sharded (long_500k, batch=1).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.api import Axes, shard
from repro.nn.layers import ACT_DTYPE, apply_rope, normal_init, rms_norm

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, tp: int):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq = cfg.padded_heads(tp)
    hkv = cfg.padded_kv_heads(tp)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    o_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "wq": normal_init(k1, (d, hq, hd), 0.02),
        "wk": normal_init(k2, (d, hkv, hd), 0.02),
        "wv": normal_init(k3, (d, hkv, hd), 0.02),
        "wo": normal_init(k4, (hq, hd, d), o_scale),
    }
    ax = {
        "wq": Axes("embed_fsdp", "heads", None),
        "wk": Axes("embed_fsdp", "kv_heads", None),
        "wv": Axes("embed_fsdp", "kv_heads", None),
        "wo": Axes("heads", None, "embed_fsdp"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), jnp.float32)
        p["bk"] = jnp.zeros((hkv, hd), jnp.float32)
        p["bv"] = jnp.zeros((hkv, hd), jnp.float32)
        ax["bq"] = Axes("heads", None)
        ax["bk"] = Axes("kv_heads", None)
        ax["bv"] = Axes("kv_heads", None)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
        ax["q_norm"] = Axes(None)
        ax["k_norm"] = Axes(None)
    return p, ax


def _project_qkv(p: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """x: (B, S, D) -> q (B,S,Hq,hd), k,v (B,S,Hkv,hd); RoPE + qk_norm."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(ACT_DTYPE))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(ACT_DTYPE))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(ACT_DTYPE))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(ACT_DTYPE)
        k = k + p["bk"].astype(ACT_DTYPE)
        v = v + p["bv"].astype(ACT_DTYPE)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_theta:
        # RoPE for decoders; also used as the positional scheme for the
        # encoder-only archs (stand-in for HuBERT's conv pos-emb; DESIGN §5)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _grouped_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B,Sq,Hq,hd), k (B,Sk,Hkv,hd) -> (B,Hkv,G,Sq,Sk) fp32 logits."""
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    s = jnp.einsum("bqhgk,bshk->bhgqs", qg, k).astype(jnp.float32)
    return s / math.sqrt(hd)


def _grouped_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs (B,Hkv,G,Sq,Sk), v (B,Sk,Hkv,hd) -> (B,Sq,Hq,hd)."""
    b, hkv, g, sq, sk = probs.shape
    out = jnp.einsum("bhgqs,bshk->bqhgk", probs.astype(v.dtype), v)
    return out.reshape(b, sq, hkv * g, v.shape[-1])


def full_attention(q, k, v, *, causal: bool, q_offset: int = 0) -> jax.Array:
    scores = _grouped_scores(q, k)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _grouped_out(probs, v)


def chunked_attention(q, k, v, *, causal: bool, chunk: int = 2048) -> jax.Array:
    """lax.map over query chunks; each chunk attends the full masked KV."""
    b, s, hq, hd = q.shape
    if s <= chunk:
        return full_attention(q, k, v, causal=causal)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    qc = q.reshape(b, nc, chunk, hq, hd).transpose(1, 0, 2, 3, 4)  # (nc,B,c,H,hd)

    @jax.checkpoint  # probs recomputed in bwd: residual = one q chunk
    def one(args):
        i, qi = args
        return full_attention(qi, k, v, causal=causal, q_offset=i * chunk)

    outs = jax.lax.map(one, (jnp.arange(nc), qc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, hq, hd)


def decode_attention(q, k_cache, v_cache, kv_len) -> jax.Array:
    """q (B,1,Hq,hd) vs cache (B,Skv,Hkv,hd); positions >= kv_len masked.

    Written as an ordinary softmax so GSPMD handles a sequence-sharded
    cache (long_500k) by partial-max/partial-sum collectives.
    """
    scores = _grouped_scores(q, k_cache)                 # (B,Hkv,G,1,Skv)
    skv = k_cache.shape[1]
    mask = jnp.arange(skv)[None, :] < jnp.asarray(kv_len)[..., None]  # (B,Skv) or (1,Skv)
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _grouped_out(probs, v_cache)


def flash_attention(q, k, v, *, causal: bool, bq: int = 512, bk: int = 512):
    """Pallas flash kernel, shard_map'd over (batch, heads) when a mesh is
    active. q (B,S,Hq,hd), k/v (B,S,Hkv,hd); heads kv-major like the
    grouped-einsum path."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.api import current_mesh
    from repro.kernels.flash_attention import flash_mha

    interpret = jax.default_backend() != "tpu"

    def local(q_, k_, v_):
        b, s, hq, hd = q_.shape
        hkv = k_.shape[2]
        g = hq // hkv
        q2 = q_.transpose(0, 2, 1, 3).reshape(b * hq, s, hd)
        k2 = k_.transpose(0, 2, 1, 3).reshape(b * hkv, k_.shape[1], hd)
        v2 = v_.transpose(0, 2, 1, 3).reshape(b * hkv, v_.shape[1], hd)
        # differentiable (custom-vjp flash bwd kernels) -> usable for train
        o = flash_mha(q2, k2, v2, causal, bq, bk, g, interpret)
        return o.reshape(b, hq, s, hd).transpose(0, 2, 1, 3)

    mesh = current_mesh()
    if mesh is None:
        return local(q, k, v)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if (batch_axes and q.shape[0] % _axes_size(mesh, batch_axes) == 0) else None
    hspec = "model" if "model" in mesh.axis_names and q.shape[2] % _axes_size(mesh, ("model",)) == 0 else None
    qs = P(bspec, None, hspec, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(qs, qs, qs),
                         out_specs=qs, check_vma=False)(q, k, v)


def _axes_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def attention_block(p: dict, cfg: ModelConfig, x: jax.Array, positions, *,
                    attn_chunk: int = 2048, cache: Optional[dict] = None,
                    long_ctx: bool = False, attn_impl: str = "xla",
                    flash_bq: int = 512, flash_bk: int = 512):
    """Full attention sublayer (no norm/residual). Returns (out, new_cache).

    cache (decode): {"k": (B,Skv,Hkv,hd) bf16, "v": same, "len": (B,) or ()}
    """
    q, k, v = _project_qkv(p, cfg, x, positions)
    if cache is not None:
        pos = cache["len"]
        kv_ax = "kv_seq_dp" if long_ctx else "kv_seq"
        quant = "k_s" in cache
        if quant:
            kq, ks = _kv_quantize(k)
            vq, vs = _kv_quantize(v)
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, pos, axis=1)
            ks_c = jax.lax.dynamic_update_slice_in_dim(cache["k_s"], ks, pos, axis=1)
            vs_c = jax.lax.dynamic_update_slice_in_dim(cache["v_s"], vs, pos, axis=1)
            k_full = _kv_dequantize(k_cache, ks_c)
            v_full = _kv_dequantize(v_cache, vs_c)
            k_full = shard(k_full, "batch", kv_ax, "kv_heads", None)
            v_full = shard(v_full, "batch", kv_ax, "kv_heads", None)
            out = decode_attention(q, k_full, v_full, pos + 1)
            new_cache = {"k": k_cache, "v": v_cache, "k_s": ks_c, "v_s": vs_c,
                         "len": pos + 1}
        else:
            # write the single new (k, v) at position pos. For a
            # sequence-sharded cache (long_ctx) use the shard-local one-hot
            # blend (no collective); otherwise dynamic_update_slice touches
            # only one page.
            if long_ctx:
                k_cache = _write_kv(cache["k"], k, pos)
                v_cache = _write_kv(cache["v"], v, pos)
            else:
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
            k_cache = shard(k_cache, "batch", kv_ax, "kv_heads", None)
            v_cache = shard(v_cache, "batch", kv_ax, "kv_heads", None)
            out = decode_attention(q, k_cache, v_cache, pos + 1)
            new_cache = {"k": k_cache, "v": v_cache, "len": pos + 1}
    else:
        if attn_impl == "flash":
            out = flash_attention(q, k, v, causal=cfg.causal, bq=flash_bq,
                                  bk=flash_bk)
        elif x.shape[1] > attn_chunk:
            out = chunked_attention(q, k, v, causal=cfg.causal, chunk=attn_chunk)
        else:
            out = full_attention(q, k, v, causal=cfg.causal)
        new_cache = None
    out = shard(out, "batch", None, "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(ACT_DTYPE))
    return y, new_cache


def _write_kv(cache: jax.Array, new: jax.Array, pos) -> jax.Array:
    """cache (B,Skv,Hkv,hd); new (B,1,Hkv,hd); write at seq position pos."""
    b = cache.shape[0]
    onehot = (jnp.arange(cache.shape[1]) == pos).astype(cache.dtype)  # (Skv,)
    return cache * (1 - onehot)[None, :, None, None] + new.astype(cache.dtype) * onehot[None, :, None, None]


def init_decode_cache(cfg: ModelConfig, batch: int, skv: int, tp: int,
                      dtype=jnp.bfloat16, quant: bool = False):
    hkv, hd = cfg.padded_kv_heads(tp), cfg.resolved_head_dim
    if quant:
        # int8 KV cache with per-(token, head) absmax scales: 8x less HBM
        # than fp32 / 2x less than bf16, and the decode memory bound is the
        # cache read (EXPERIMENTS §Perf, decode iteration)
        return {
            "k": jnp.zeros((batch, skv, hkv, hd), jnp.int8),
            "v": jnp.zeros((batch, skv, hkv, hd), jnp.int8),
            "k_s": jnp.zeros((batch, skv, hkv), jnp.bfloat16),
            "v_s": jnp.zeros((batch, skv, hkv), jnp.bfloat16),
            "len": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, skv, hkv, hd), dtype),
        "v": jnp.zeros((batch, skv, hkv, hd), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def _kv_quantize(x: jax.Array):
    """x (B,1,Hkv,hd) -> (int8 codes, bf16 scale (B,1,Hkv))."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1), 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s.astype(jnp.bfloat16)


def _kv_dequantize(q: jax.Array, s: jax.Array) -> jax.Array:
    return (q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)[..., None])
