"""Production meshes (defined as functions — importing this module never
touches jax device state).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
carries cross-pod data parallelism (and FSDP participation for the largest
models); `model` stays intra-pod where ICI is fastest.
"""
from __future__ import annotations

import jax

from ..sharding.partition import MeshInfo


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place
    activations with ``with_sharding_constraint``, which only accepts
    Auto mesh axes (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_mesh_info(mesh) -> MeshInfo:
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return MeshInfo(mesh=mesh, dp=dp, tp="model")


def make_host_mesh(n_model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    assert n % n_model == 0
    return make_auto_mesh((n // n_model, n_model), ("data", "model"))
