"""Production meshes.

A FUNCTION (not module-level state) so importing never touches jax device
initialisation -- the dry-run sets XLA_FLAGS before any jax call, and smoke
tests must keep seeing 1 CPU device."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:   (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small host-device mesh for CPU integration tests."""
    return jax.make_mesh(shape, axes)


def auto_axes(mesh):
    """The same devices and axis names with every axis ``Auto``: shardings
    propagate through the partitioner, as the sharding hints in
    ``models/layers.py`` assume, instead of being typed per op (the
    ``jax.make_mesh`` default, under which gathers and sharded
    contractions need an explicit ``out_sharding``)."""
    return jax.sharding.Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes the batch dimension shards over (pod joins data-parallel in the
    baseline multi-pod configuration)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
