"""Production meshes (DESIGN.md §6).

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run sets XLA_FLAGS --xla_force_host_platform_device_count
*before* any jax import (see dryrun.py) to obtain 256/512 host devices.

Every mesh axis is ``Auto``: the step functions place data with explicit
``shard_map`` specs and collectives, which is what Auto axes mean.  (Since
jax 0.7 ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
same programs fail with sharding-type errors.)
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 chips per pod; 2 pods when multi_pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes_of(mesh):
        out *= mesh.shape[a]
    return out


def tp_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
