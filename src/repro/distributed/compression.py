"""Gradient compression: int8 all-reduce with error feedback, bf16 cast.

Under GSPMD, data-parallel gradient reduction is fused into the backward
pass automatically, so compression must be expressed as an EXPLICIT
collective: ``compressed_psum`` is a shard_map building block that
quantizes (int8 + per-block absmax scale), psums the codes, and
dequantizes, carrying an error-feedback residual so the bias vanishes over
steps. The DGNN trainer uses it end-to-end (replicated params, batch
sharded over streams); for the LM path it is available to a manual-DP
train step and benchmarked in benchmarks/compression_bench.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_BLOCK = 256


def _quant(x: jax.Array):
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    npad = (n + _BLOCK - 1) // _BLOCK * _BLOCK
    padded = jnp.pad(flat, (0, npad - n)).reshape(-1, _BLOCK)
    scale = jnp.maximum(jnp.max(jnp.abs(padded), axis=1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(padded / scale[:, None]), -127, 127).astype(jnp.int8)
    err = (padded - q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n].reshape(x.shape)
    return q, scale, err


def _dequant(q, scale, shape):
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape)


def _quant_with_scale(x: jax.Array, scale: jax.Array):
    """Quantize with a GIVEN per-block scale; returns (codes, residual)."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    npad = (n + _BLOCK - 1) // _BLOCK * _BLOCK
    padded = jnp.pad(flat, (0, npad - n)).reshape(-1, _BLOCK)
    q = jnp.clip(jnp.round(padded / scale[:, None]), -127, 127).astype(jnp.int8)
    err = (padded - q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n].reshape(x.shape)
    return q, err


def compressed_psum(x: jax.Array, residual: jax.Array, axis: str):
    """int8-compressed psum over ``axis`` with error feedback.

    Call INSIDE shard_map. Returns (mean-reduced value, new residual).
    Protocol: (1) pmax the per-block absmax scales (tiny), (2) every shard
    quantizes against the SHARED scale, (3) psum the int8 codes in int32,
    (4) dequantize. The only loss is local quantization error, which is
    exactly what the error-feedback residual carries to the next step —
    the estimate is unbiased over steps. Wire bytes: ~1/4 of fp32.
    """
    y = x + residual
    flat = y.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    npad = (n + _BLOCK - 1) // _BLOCK * _BLOCK
    padded = jnp.pad(flat, (0, npad - n)).reshape(-1, _BLOCK)
    local_scale = jnp.maximum(jnp.max(jnp.abs(padded), axis=1) / 127.0, 1e-12)
    scale = jax.lax.pmax(local_scale, axis)          # shared per-block scale
    q, err = _quant_with_scale(y, scale)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis)
    cnt = jax.lax.psum(1, axis)
    mean = _dequant(qsum, scale, y.shape) / cnt
    return mean, err


def bf16_psum(x: jax.Array, axis: str) -> jax.Array:
    """Half-precision gradient reduction (2x wire bytes saved)."""
    return jax.lax.psum(x.astype(jnp.bfloat16), axis).astype(jnp.float32) / jax.lax.psum(1, axis)


def make_compressed_grad_fn(loss_fn, mesh, batch_axes=("data",),
                            scheme: str = "int8"):
    """Wrap a per-example loss into a shard_map'd compressed-DP grad fn.

    loss_fn(params, batch) -> scalar (mean over local batch).
    Returns grad_fn(params, residuals, batch) -> (grads, new_residuals, loss).
    params replicated; batch sharded on its leading axis over ``batch_axes``.
    """
    axis = batch_axes[0]

    def body(params, residuals, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        if scheme == "int8":
            flat_g, treedef = jax.tree.flatten(grads)
            flat_r = jax.tree.leaves(residuals)
            outs = [compressed_psum(g, r, axis) for g, r in zip(flat_g, flat_r)]
            grads = jax.tree.unflatten(treedef, [o[0] for o in outs])
            new_res = jax.tree.unflatten(treedef, [o[1] for o in outs])
        elif scheme == "bf16":
            grads = jax.tree.map(lambda g: bf16_psum(g, axis), grads)
            new_res = residuals
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
            new_res = residuals
        return grads, new_res, loss

    rep = P()

    def grad_fn(params, residuals, batch):
        batch_specs = jax.tree.map(lambda _: P(axis), batch)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, params),
                      jax.tree.map(lambda _: rep, residuals),
                      batch_specs),
            out_specs=(jax.tree.map(lambda _: rep, params),
                       jax.tree.map(lambda _: rep, residuals),
                       rep),
            check_vma=False,
        )(params, residuals, batch)

    return grad_fn


def init_residuals(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
