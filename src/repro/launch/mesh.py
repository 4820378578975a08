"""Production device meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and smoke tests/benches must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (jax defaults to Explicit): the
    models steer GSPMD with sharding constraints rather than typed axes.
    ``devices`` defaults to every local device."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(tuple(axis_names)),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one TPU-v5e-like pod,
    2D torus). Multi-pod: (pod=2, data=16, model=16) = 512 chips; the pod
    axis is pure data parallelism across the DCI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, *, pods: int = 0):
    """Small mesh for CPU integration tests (requires forced host devices)."""
    if pods:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def mesh_axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)
