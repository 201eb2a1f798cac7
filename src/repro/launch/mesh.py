"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's
``xla_force_host_platform_device_count`` trick and for tests that must see
one device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _require_devices(shape, axes) -> list:
    """The first ``prod(shape)`` devices, or a clear error.

    ``jax.devices()[:n]`` silently under-fills when fewer devices exist and
    ``make_mesh`` then fails with an opaque reshape error — raise here with
    the fix spelled out instead.
    """
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        req = "×".join(f"{a}={s}" for a, s in zip(axes, shape))
        raise ValueError(
            f"mesh ({req}) needs {n} devices but jax.devices() provides "
            f"{len(devices)}; shrink the mesh or launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(set before jax imports)")
    return devices[:n]


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 = 256 chips; multi-pod adds a leading pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = _require_devices(shape, axes)  # dry-run exposes 512 host devs
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host devices — tests/examples/sharded serving."""
    axes = ("data", "model")
    devices = _require_devices((data, model), axes)
    return jax.make_mesh((data, model), axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    """Axes a global batch shards over (pod+data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def fsdp_axes(mesh) -> tuple:
    """Axes FSDP parameter sharding uses at training time."""
    return batch_axes(mesh)
