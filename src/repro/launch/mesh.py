"""Production mesh construction (deliverable e).

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import so these shapes are buildable on the CPU container.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: (16, 16) = 256 chips single-pod; (2, 16, 16) = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary test/CI mesh with Auto axis types."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(axes))


def make_client_mesh(num_shards: int = 0):
    """1-D mesh laying FL clients out over devices (axis name ``clients``).

    ``num_shards=0`` uses every local device. The FL engines shard the
    sampled-client leading axis over this mesh; the mesh size must divide
    the per-round client count (each shard takes clients/shards rows).
    """
    n = num_shards or jax.device_count()
    if n > jax.device_count():
        raise ValueError(
            f"requested {n} shards but only {jax.device_count()} devices are "
            "visible (set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before the first jax import to fake CPU devices)"
        )
    return make_mesh((n,), ("clients",))


def mesh_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def has_pod_axis(mesh) -> bool:
    return "pod" in mesh.axis_names
