"""Names of the matrices whose norms a train cell's check compares.

One rule names the program's parameters and every reference's alike: a
matrix is named by its place in the model, its keys joined by ``/``.

* Outside the blocks, its key path: ``embed``, ``head``,
  ``final_norm/scale``.
* In the block of layer ``i``, the layers numbered from 0 over the whole
  model: ``layers/<i>/`` and its key path in the block, as
  ``layers/0/mix/wq``, ``layers/3/ffn/shared/w_in``, ``layers/2/ln1/scale``.
* A routed expert's weight is one matrix per expert: its leaf's name and
  ``/<expert>``, as ``layers/3/ffn/w_in/17``.

A tree's leaves lead with the replica axis, then with the leaf's stacked
axes (layers, experts), each index of which is a matrix of its own; a
matrix's norm is over the axes that remain.  Which axes a tree stacks, and
how its paths map onto the names, is the tree's own: ``bench/train_cell.py``
says it for the program, each ``bench/reference/<model>.py`` for its
reference.  Two matrices that would share a name raise.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np


def keys_of(path) -> Tuple:
    """A JAX key path as plain dict keys and list indices."""
    return tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)


def join(keys: Sequence) -> str:
    return "/".join(str(k) for k in keys)


def path_norms(tree, stacked: Callable[[Tuple], int]) -> Dict[str, object]:
    """Per leaf, under its joined key path: the L2 norm over every axis
    after the replica axis and the ``stacked(keys)`` stacked axes that
    follow it (traceable)."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = keys_of(path)
        lead = 1 + stacked(keys)
        out[join(keys)] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                           axis=tuple(range(lead, x.ndim))))
    return out


def named(norms: Dict[str, object],
          name_of: Callable[[str, Tuple[int, ...]], str],
          ) -> Dict[str, np.ndarray]:
    """One (replicas,) array per matrix: each leaf's norms (see
    :func:`path_norms`) split over its stacked axes, the matrix at stacked
    index ``i`` of path ``p`` named ``name_of(p, i)``."""
    out: Dict[str, np.ndarray] = {}
    where: Dict[str, str] = {}
    for path, x in norms.items():
        x = np.asarray(x)
        for index in np.ndindex(x.shape[1:]):
            name = name_of(path, tuple(int(i) for i in index))
            if name in out:
                raise ValueError(f"two matrices named {name!r}: {where[name]} "
                                 f"and {path} at {index}")
            where[name] = f"{path} at {index}"
            out[name] = x[(slice(None),) + index]
    return out


def flat(norms: Dict[str, np.ndarray]) -> Tuple[List[str], np.ndarray]:
    """Names in order and a (replicas, matrices) array of their norms."""
    names = sorted(norms)
    return names, np.stack([np.asarray(norms[n]) for n in names], axis=1)
