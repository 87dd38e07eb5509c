"""Compile-only checks of the stream engine for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology. These tests lower the batched
stream-engine launches at the production widths of the paper's models —
in_dim 64, hidden 128, 2 GCN layers, edge messages, plan defaults n_pad
640 / k_max 64 / e_pad 4096, a BC-Alpha-sized global store (578 x 6
nodes), B=4 streams of T=8 snapshots — with Pallas interpret mode off,
and assert the kernel reached Mosaic (``tpu_custom_call``). They catch
what interpret-mode tests cannot: block shapes that break the TPU tiling
rule, in-kernel ops with no Mosaic lowering, VMEM over the limit. Nothing
runs; a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture — never at
import — because only one process may load the TPU library at a time: a
worker that imports this file must not touch it unless it runs the tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

B, T, N, K, DIN, H, OUT, E = 4, 8, 640, 64, 64, 128, 64, 4096
G = 578 * 6


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # loading the TPU compiler otherwise writes its logs to a fixed
    # directory shared by every process on the machine
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _launch_args(family: str, sharding, B: int = B):
    """ShapeDtypeStructs of one batched launch's ops-level arguments."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    ell = (s((B, T, N, K), jnp.int32), s((B, T, N, K)))
    if family == "gcrn":
        return (*ell, s((B, T, N, K), jnp.int32), s((B, T, N, DIN)),
                s((B, T, N), jnp.int32), s((B, T, N)), s((B, G, H)),
                s((B, G, H)), s((DIN, 4 * H)), s((H, 4 * H)), s((4 * H,)),
                s((B, T, E, DIN)))
    if family == "stacked":
        return (*ell, s((B, T, N, K), jnp.int32), s((B, T, N, DIN)),
                s((B, T, N), jnp.int32), s((B, T, N)), s((B, G, H)),
                s((DIN, H)), s((H,)), s((H, 3 * H)), s((H, 3 * H)),
                s((3 * H,)), s((B, T, E, DIN)))
    if family == "tgn":
        return (*ell, s((B, T, N, K)), s((B, T, N, DIN)),
                s((B, T, N), jnp.int32), s((B, T, N)), s((B, G, H)),
                s((H,)), s((DIN, H)), s((H, 3 * H)), s((H, 3 * H)),
                s((3 * H,)))
    dims = [(DIN, H), (H, OUT)]
    if family == "evolve":
        return (*ell, s((B, T, N, DIN)), s((B, T, N)), s((B, T), jnp.int32),
                [s((B,) + d) for d in dims], [s((d[1],)) for d in dims],
                [s((d[0], 3 * d[0])) for d in dims],
                [s((d[0], 3 * d[0])) for d in dims],
                [s((3 * d[0],)) for d in dims],
                [s((B, T, N, d[0])) for d in dims])
    assert family == "static_gcn"
    return (s((B * T, 1, N, K), jnp.int32), s((B * T, 1, N, K)),
            s((B * T, 1, N, DIN)), s((B * T, 1, N)),
            [s(d) for d in dims], [s((d[1],)) for d in dims],
            [s((B * T, 1, N, d[0])) for d in dims])


@pytest.mark.parametrize("family,residency", [
    ("gcrn", "vmem"), ("evolve", "vmem"), ("stacked", "vmem"),
    ("tgn", "vmem"), ("static_gcn", "vmem"),
    ("gcrn", "hbm_paged"), ("evolve", "hbm_paged"),
    ("stacked", "hbm_paged"), ("tgn", "hbm_paged"),
])
def test_stream_launch_compiles_for_v5e(one_chip, monkeypatch, family,
                                        residency):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    kw = dict(tn=128)
    if residency == "hbm_paged":
        kw.update(td=128, state_residency="hbm_paged", buffer_depth=2)
    if family != "static_gcn":
        kw["lengths"] = jnp.full((B,), T, jnp.int32)

    def launch(*args):
        return ops.stream_steps_batched(family, *args, **kw)

    compiled = jax.jit(launch).lower(*_launch_args(family, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family", ["gcrn", "evolve"])
def test_batch8_launch_fits_vmem_limit(one_chip, monkeypatch, family):
    """B=8 streams on one chip (the reference batch of the 4-chip smoke):
    the resident GCRN-M2 launch needs more than the compiler's default
    16 MiB of scoped VMEM, so the engine must ask for its own limit."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    b = 8

    def launch(*args):
        return ops.stream_steps_batched(family, *args, tn=128,
                                        lengths=jnp.full((b,), T, jnp.int32))

    args = _launch_args(family, one_chip, B=b)
    compiled = jax.jit(launch).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
