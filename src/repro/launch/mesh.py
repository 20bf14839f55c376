"""Production meshes. Functions, not module constants — importing this module
never touches jax device state (the dry-run sets XLA_FLAGS first)."""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape, axes):
    """`jax.make_mesh` with `Auto` axes: sharding is propagated by the
    compiler from the in/out shardings, as the model code assumes (jax's
    default `Explicit` axes would demand an `out_sharding` on every gather
    such as the embedding lookup)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis is
    pure extra data parallelism (lowest ICI traffic across the DCN/pod
    boundary, DESIGN §6)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_sa_mesh(p: int | None = None, axis: str = "bsp"):
    """1-D mesh for the BSP suffix-array pipeline (the paper's p)."""
    devs = jax.devices()
    p = p or len(devs)
    return jax.sharding.Mesh(np.array(devs[:p]).reshape(p), (axis,))


def mesh_num_devices(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
