"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  Single-pod: (16, 16) ("data", "model") = 256
chips.  Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips; the
``pod`` axis is the Enoki replication domain (DCN), the inner axes are ICI.

Meshes use ``AxisType.Auto`` axes (``jax.make_mesh`` defaults to
``Explicit``): the sharding rules here annotate with ``NamedSharding`` and
let the compiler propagate.
"""
from __future__ import annotations

from typing import Sequence

import jax

from repro.configs.base import MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_from_config(cfg: MeshConfig):
    return make_mesh(cfg.shape, cfg.axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh for CPU integration tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=prod(shape))."""
    return make_mesh(shape, axes)
