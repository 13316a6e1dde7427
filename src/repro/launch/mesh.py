"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.  The dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before importing
jax; everything else sees the real device count.

Every axis is ``AxisType.Auto``: the repo places shardings through
GSPMD constraints and ``shard_map``, never through explicit axis types.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto(n_axes: int) -> tuple[AxisType, ...]:
    return (AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests, benchmarks, elastic restarts)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over the first ``data * model`` devices of this host."""
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"), axis_types=_auto(2))
