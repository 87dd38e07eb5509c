"""Driver of the plain references: one tenant's snapshot stream, step by
step, in float32 ``jax.numpy``.

Imports nothing of the program. Each family's equations live in
``reference/<family>.py`` as ``step(params, state, g, m, dot)`` over one
snapshot ``g``, prepared here from its raw COO edges: active nodes
renumbered in ascending global id, edges made undirected, a self-loop per
node, and the symmetric GCN coefficient ``1 / sqrt(deg(u) deg(v))`` over
in-degrees of that graph. The arrays are padded to fixed sizes only so
that one compiled scan serves every stream: padding edges carry
coefficient 0 into a spare segment, padding nodes read and write a spare
store row, and neither reaches a compared number.

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


def prepare(src: np.ndarray, dst: np.ndarray, edge_feat: np.ndarray,
            n_rows: int, n_edges: int, sink: int) -> dict:
    """One snapshot's padded reference arrays (numpy), from global-id COO
    edges. ``sink`` is the spare store row that padding nodes use."""
    active = np.unique(np.concatenate([src, dst]))
    n = active.size
    s = np.searchsorted(active, src)
    d = np.searchsorted(active, dst)
    loops = np.arange(n)
    es = np.concatenate([s, d, loops])
    ed = np.concatenate([d, s, loops])
    ef = np.concatenate([edge_feat, edge_feat,
                         np.zeros((n, edge_feat.shape[1]), np.float32)])
    deg = np.bincount(ed, minlength=n).astype(np.float64)
    coef = 1.0 / np.sqrt(deg[es] * deg[ed])
    m = es.size
    if n > n_rows or m > n_edges:
        raise ValueError(f"snapshot of {n} nodes / {m} edges exceeds the "
                         f"reference buffers ({n_rows}, {n_edges})")
    g = {"rows": np.full(n_rows, sink, np.int32),
         "src": np.zeros(n_edges, np.int32),
         "dst": np.full(n_edges, n_rows, np.int32),
         "coef": np.zeros(n_edges, np.float32),
         "ef": np.zeros((n_edges, edge_feat.shape[1]), np.float32),
         "live": np.int32(1)}
    g["rows"][:n] = active
    g["n"] = n
    g["src"][:m], g["dst"][:m] = es, ed
    g["coef"][:m], g["ef"][:m] = coef, ef
    return g


def max_in_degree(src: np.ndarray, dst: np.ndarray) -> int:
    """Largest in-degree of the undirected graph with self-loops."""
    active = np.unique(np.concatenate([src, dst]))
    s = np.searchsorted(active, src)
    d = np.searchsorted(active, dst)
    return int(np.bincount(np.concatenate([d, s]),
                           minlength=active.size).max()) + 1


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
               feat: np.ndarray, precision: str):
    """One tenant's stream through ``family``'s reference from a fresh
    state. ``prepared`` holds the stream's snapshots as ``prepare`` made
    them, ``feat`` is the global node-feature table. Returns
    ``(outputs, final_state)``: outputs a list of ``(n_active, out_dim)``
    numpy arrays, the state in the program's tree, as numpy."""
    fn = _block_fn(family, tuple(sorted(m.items())), precision)
    state = family.to_store(
        family.init_state(params, m, n_global, dot_fn(precision)))
    outs = []
    for b0 in range(0, len(prepared), BLOCK):
        blk = prepared[b0:b0 + BLOCK]
        blk = blk + [dict(blk[-1], live=np.int32(0))] * (BLOCK - len(blk))
        stacked = {k: np.stack([g[k] for g in blk]) for k in blk[0]
                   if k != "n"}
        stacked["x"] = feat[np.minimum(stacked["rows"], n_global - 1)]
        state, o = fn(params, state, stacked)
        o = np.asarray(o)
        outs.extend(o[i, :g["n"]]
                    for i, g in enumerate(prepared[b0:b0 + BLOCK]))
    return outs, jax.tree.map(np.asarray, family.from_store(state))
