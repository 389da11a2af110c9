"""The few jax mesh/shard_map calls the engine makes, in one place.

Everything engine/launch-side builds meshes and shard_maps through these
helpers so the BSP core has exactly one place that knows the jax surface.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off.

    The mailbox all_to_all produces per-device blocks whose replication the
    checker cannot infer (same reason the upstream code passes
    ``check_vma=False``), so the check is always disabled.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` over the first ``prod(shape)`` devices, Auto axes."""
    shape = tuple(shape)
    axes = tuple(axes)
    if devices is None:
        n = 1
        for s in shape:
            n *= s
        devices = jax.devices()[:n]
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def abstract_mesh(shape, axes):
    """A device-free mesh of the given axis sizes and names, for tracing
    and lowering shard_map loops without the devices (static analysis)."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))
