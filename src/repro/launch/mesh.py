"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before the first jax call.  Install a mesh as the
ambient one with ``jax.set_mesh(mesh)``.

Mesh shapes (TPU v5e):
  single-pod: (data=16, model=16)              — 256 chips
  multi-pod:  (pod=2, data=16, model=16)       — 512 chips
"""
from __future__ import annotations

import math

import jax


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (its default is
    ``Explicit``): the model code places activations with sharding hints
    and leaves the rest to the partitioner."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh with the production axis names (CPU tests)."""
    return auto_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """The mesh axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_devices(mesh) -> int:
    return math.prod(mesh.shape.values())
