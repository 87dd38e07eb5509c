"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is a
second data-parallel axis with slower (DCI) links — collectives crossing it
are what the multi-pod dry-run must prove out.

Functions, not module constants: importing this module never touches jax
device state (smoke tests see 1 CPU device; only dryrun.py forces 512).
The model-parallel meshes use Auto axes: the models place activations
with ``with_sharding_constraint``, which ``jax.make_mesh``'s default
Explicit axes reject.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


@dataclass(frozen=True)
class DeviceSpec:
    """Device sharding of a batched stream launch.

    ``n_devices`` shards the LEADING B grid axis of the stream engine
    (kernels/ops.stream_steps_batched) via shard_map over a 1-D data mesh:
    each device runs an independent slice of the stream batch (streams
    never communicate — their recurrent states are per-stream), so the
    sharded launch is bit-identical to the unsharded one. The default
    (n_devices=1) is the plain single-device launch with no mesh at all.
    ``axis`` is the mesh axis name (the 'data' axis of the production
    meshes above).
    """

    n_devices: int = 1
    axis: str = "data"


def make_stream_mesh(spec: DeviceSpec) -> Mesh:
    """1-D mesh for sharding a stream batch per ``DeviceSpec``."""
    devs = jax.devices()
    if len(devs) < spec.n_devices:
        raise RuntimeError(
            f"DeviceSpec wants {spec.n_devices} devices, have {len(devs)} — "
            "use XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU")
    return jax.make_mesh((spec.n_devices,), (spec.axis,),
                         devices=devs[:spec.n_devices])


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices for mesh {shape}, have {len(devs)} — "
            "run under dryrun.py (XLA_FLAGS=--xla_force_host_platform_device_count=512)")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> Mesh:
    """1x1 mesh on the real local device (smoke tests / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(AxisType.Auto,) * 2)
