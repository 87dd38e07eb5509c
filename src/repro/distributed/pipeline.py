"""Pipeline parallelism (GPipe-style) over a 'stage' mesh axis.

The assigned production meshes (16x16, 2x16x16) don't carry a stage axis —
DP x TP(+FSDP) covers every assigned arch — so PP is not wired into the
dry-run. It exists as a first-class building block for deeper-than-memory
models on other meshes (DESIGN §6), implemented the jax-native way:

  - layers are grouped into S stages; stage s's parameters are sharded to
    mesh axis 'stage' index s (one stage per stage-axis slice);
  - a lax.scan over (microbatches + S - 1) clock ticks shifts activations
    stage-to-stage with ppermute (the classic skewed-pipeline schedule);
  - every tick, ALL stages run their block on their current microbatch —
    bubbles at the ends are masked out.

``pipeline()`` is written against shard_map: callers provide the per-stage
block function and stacked per-stage params.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline(block_fn: Callable, mesh, n_stages: int, n_micro: int,
             stage_axis: str = "stage"):
    """Build a pipelined forward: (stage_params, x_micro) -> y_micro.

    block_fn(params_slice, x) -> y — one stage's computation.
    stage_params: pytree with leading dim n_stages (sharded over the stage
    axis). x_micro: (n_micro, mb, ...) microbatched input (replicated over
    the stage axis; only stage 0 consumes it).
    """

    def per_shard(params, xs):
        # params: this stage's slice (leading dim 1); xs: (n_micro, mb, ...)
        sid = jax.lax.axis_index(stage_axis)
        p = jax.tree.map(lambda a: a[0], params)
        mb_shape = xs.shape[1:]
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros(mb_shape, xs.dtype)          # current stage input
        outs = jnp.zeros((n_micro, *mb_shape), xs.dtype)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when valid)
            x_in = jnp.where(t < n_micro,
                             xs[jnp.minimum(t, n_micro - 1)],
                             jnp.zeros(mb_shape, xs.dtype))
            cur = jnp.where(sid == 0, x_in, buf)
            y = block_fn(p, cur)
            # shift to the next stage
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage emits microbatch t - (n_stages - 1)
            out_idx = t - (n_stages - 1)
            valid = (sid == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
                lambda o: o,
                outs)
            return (nxt, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast via psum-mask
        mask = (sid == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, stage_axis)

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def reference_stack(block_fn: Callable, stage_params, xs):
    """Unpipelined oracle: run stages sequentially on each microbatch."""
    def one(x):
        for s in range(jax.tree.leaves(stage_params)[0].shape[0]):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = block_fn(p, x)
        return x

    return jax.vmap(one)(xs)
