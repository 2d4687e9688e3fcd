"""Meshes and their data-parallel and model axes.

Defined as FUNCTIONS so importing this module never touches jax device
state.

Every mesh axis is ``Auto``: the step functions place data with explicit
``shard_map`` specs and collectives, which is what Auto axes mean.  (Since
jax 0.7 ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
same programs fail with sharding-type errors.)
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes_of(mesh) -> Tuple[str, ...]:
    """The data-parallel axes: ``("data",)``, or none on a mesh without one."""
    return ("data",) if "data" in mesh.axis_names else ()


def dp_size(mesh) -> int:
    return mesh.shape["data"] if "data" in mesh.axis_names else 1


def tp_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
