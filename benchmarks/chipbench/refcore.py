"""Driver of the plain references: one tenant's stream, step by step, in
float32 ``jax.numpy``.

Imports nothing of the program. Each family's equations live in
``reference/<family>.py`` as ``step(params, state, g, m, dot)`` over one
step ``g``, the padded arrays that the stream's kind prepares
(``streams/<kind>.py``). The padding serves one compiled scan for every
stream and reaches no compared number.

``dot`` is the precision under test: ``"highest"`` is the reference,
``"high"`` the control, which rounds each operand to a bfloat16 pair and
keeps three of the four products (what a TPU's ``Precision.HIGH`` does),
spelled out so that it means the same on any backend. The rounding is
``lax.reduce_precision``, which XLA keeps: a float32 -> bfloat16 ->
float32 round trip of ``astype`` may be folded away where XLA allows
excess precision, as it does on the TPU, and the pair then degenerates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: steps per compiled block; a stream runs in blocks of this many, the
#: last one padded with steps that change nothing
BLOCK = 64


def dot_fn(precision: str):
    """``a @ b`` at the named precision (see the module docstring)."""
    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return lambda a, b: jnp.matmul(a, b, precision=hi)
    if precision == "high":
        def bf16(x):
            return jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=7)

        def dot3(a, b):
            a1, b1 = bf16(a), bf16(b)
            a2, b2 = bf16(a - a1), bf16(b - b1)
            return (jnp.matmul(a1, b1, precision=hi)
                    + jnp.matmul(a1, b2, precision=hi)
                    + jnp.matmul(a2, b1, precision=hi))
        return dot3
    raise ValueError(f"unknown reference precision {precision!r}")


def aggregate(g: dict, msg: jax.Array) -> jax.Array:
    """``sum over edges (u -> v) of coef * msg[u -> v]`` for every node v,
    from one message per edge."""
    n = g["rows"].shape[0]
    return jax.ops.segment_sum(msg * g["coef"][:, None], g["dst"],
                               num_segments=n + 1)[:n]


@functools.lru_cache(maxsize=None)
def _block_fn(family, dims: tuple, precision: str):
    m = dict(dims)
    dot = dot_fn(precision)

    def body(carry, g):
        params, state = carry
        new_state, out = family.step(params, state, g, m, dot)
        live = g["live"] > 0
        state = jax.tree.map(lambda a, b: jnp.where(live, a, b),
                             new_state, state)
        return (params, state), out

    def run(params, state, block):
        (_, state), outs = jax.lax.scan(body, (params, state), block)
        return state, outs

    return jax.jit(run)


def run_stream(family, params, m: dict, n_global: int, prepared: list,
               precision: str):
    """One tenant's stream through ``family``'s reference from a fresh
    state. ``prepared`` holds the stream's steps as its kind's ``prepare``
    made them. Returns ``(outputs, final_state)``: outputs a list of
    ``(n, out_dim)`` numpy arrays (``n`` the step's live output rows),
    the state in the program's tree, as numpy."""
    fn = _block_fn(family, tuple(sorted(m.items())), precision)
    state = family.to_store(
        family.init_state(params, m, n_global, dot_fn(precision)))
    outs = []
    for b0 in range(0, len(prepared), BLOCK):
        blk = prepared[b0:b0 + BLOCK]
        blk = blk + [dict(blk[-1], live=np.int32(0))] * (BLOCK - len(blk))
        stacked = {k: np.stack([g[k] for g in blk]) for k in blk[0]
                   if k != "n"}
        state, o = fn(params, state, stacked)
        o = np.asarray(o)
        outs.extend(o[i, :g["n"]]
                    for i, g in enumerate(prepared[b0:b0 + BLOCK]))
    return outs, jax.tree.map(np.asarray, family.from_store(state))
