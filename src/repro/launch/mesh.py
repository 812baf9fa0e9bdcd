"""Mesh construction for single-pod and multi-pod deployments.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips).

    Axes: `data` carries data parallelism + FSDP weight sharding; `model`
    carries tensor/expert/sequence parallelism; `pod` (multi-pod only) is
    pure data parallelism so only gradient all-reduces cross the
    inter-pod (DCN) boundary — the Table I lesson: WAN-class bytes are
    ~263x local-network cost, keep them out of the inner loop.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over whatever devices exist (CPU tests / small-scale drivers)."""
    n = len(jax.devices())
    if data * model > n:
        data, model = n, 1
    return _make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    size = 1
    for a in dp_axes(mesh):
        size *= mesh.shape[a]
    return size
