"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.

Mesh shapes:
  single-pod  (16, 16)      axes ("data", "model")   — 256 chips
  multi-pod   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Axis roles:
  pod    pure data parallelism across pods (gradient all-reduce crosses the
         inter-pod links once per step); optionally joins the FSDP axis for
         models that don't fit pod-local sharding (deepseek-v3 training).
  data   batch parallelism + FSDP (ZeRO-3 parameter/optimizer sharding).
  model  tensor parallelism (heads / d_ff / vocab / experts) and
         KV-cache sequence parallelism when serving.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(devices_per_axis: tuple[int, ...] = (2, 2),
                    axes: tuple[str, ...] = ("data", "model")):
    """Small mesh for CPU-host tests (requires matching device count)."""
    return jax.make_mesh(devices_per_axis, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes usable for batch sharding, largest stride first."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# The CPU-CI recipe for multi-device testing: XLA splits the host platform
# into N virtual devices.  Must be set BEFORE jax initializes (any
# jax.devices() call pins the count), which is why it is an env-var string
# here rather than a function that sets it.
HOST_DEVICE_RECIPE = 'XLA_FLAGS="--xla_force_host_platform_device_count=8"'


def make_data_mesh(num_devices: int | None = None, axis: str = "data"):
    """1-D serving mesh over the first ``num_devices`` local devices.

    This is the mesh the sharded blocked forward partitions over
    (``core.aggregate.shard_scope`` / ``aggregate_combine_sharded``): one
    named axis, conventionally "data" because destination block-rows and
    feature slices are both batch-like partitions (no tensor-parallel
    collectives beyond the feature strategy's contraction psum).

    Unlike ``make_production_mesh`` this takes a device *count*, so a
    device-scaling sweep can build 1/2/4/8-way meshes from one host
    process (started under ``HOST_DEVICE_RECIPE`` on CPU).
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    n = len(devices) if num_devices is None else int(num_devices)
    if n < 1:
        raise ValueError("num_devices must be >= 1")
    if n > len(devices):
        raise ValueError(
            f"asked for {n} devices but only {len(devices)} are visible; "
            f"on CPU hosts start the process with {HOST_DEVICE_RECIPE}")
    return Mesh(np.asarray(devices[:n]), (axis,))
