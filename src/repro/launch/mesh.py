"""Production meshes.

Single pod : (16, 16)    axes ("data", "model")   = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Every mesh is built by ``make_mesh`` with ``Auto`` axes: the train steps
steer GSPMD with ``with_sharding_constraint``, which only refers to Auto
axes (``jax.make_mesh`` defaults to Explicit ones).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(max_devices: int | None = None) -> jax.sharding.Mesh:
    """Degenerate mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices()) if max_devices is None else max_devices
    return make_mesh((1, n), ("data", "model"))


def make_data_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """Pure data-parallel 1-D mesh, axis name "data" -- the shape the
    distributed guarded reduce runs on (each device holds one shard of the
    grads; the mesh axis is the fixed-order combine's fold order). With
    ``n_devices=None`` spans every visible device (on the CI's forced
    8-way CPU host this is the 8-device test mesh)."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return make_mesh((n,), ("data",))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over (all data-parallel axes)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def replicated(mesh: jax.sharding.Mesh) -> jax.sharding.NamedSharding:
    """A full copy on every device of ``mesh``."""
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
