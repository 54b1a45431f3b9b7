"""Mesh construction: the one place this repo calls ``jax.make_mesh``.

Every mesh has ``AxisType.Auto`` axes.  Since jax 0.9 ``jax.make_mesh``
defaults to ``Explicit`` axes, which put shardings into the types: the
plan engine's shard_map programs, the GSPMD serving path and the
dry-run's ``with_sharding_constraint`` calls are all written for ``Auto``
axes and fail type checks on ``Explicit`` ones.

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax use).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh of ``shape`` named ``axes`` with ``Auto`` axis types, over
    ``devices`` (default: the first ``prod(shape)`` of ``jax.devices()``,
    in jax's topology-aware order)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod (v5e full pod); 2 pods = 512 chips when
    multi_pod.  Axes: (pod,) data, model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
