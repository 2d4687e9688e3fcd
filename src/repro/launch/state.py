"""Train state: per-replica parameters + optimizer + consistency sync state.

GLOBAL layout: every leaf carries a leading ``dp`` axis sharded over the
data-parallel mesh axes — each data-parallel replica owns a (drifting) copy,
which is exactly the paper's per-worker parameter replica.  Per-device
memory equals plain replication (DESIGN.md §3).  Inside shard_map the local
slice has leading dim 1; steps squeeze/unsqueeze it uniformly.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.sync import SyncState, init_sync_state
from repro.models import model as M
from repro.models.common import instantiate_tree
from repro.optim import OptState, init_opt_state

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt: OptState
    sync: SyncState
    step: jnp.ndarray


def init_local_state(cfg: ModelConfig, tcfg: TrainConfig, tp: int,
                     key: jax.Array) -> TrainState:
    """Single-replica state (no dp axis)."""
    defs = M.model_defs(cfg, tp)
    params = instantiate_tree(defs, key)
    sdt = jnp.dtype(tcfg.state_dtype) if tcfg.state_dtype != "float32" else None
    return TrainState(
        params=params,
        opt=init_opt_state(params, tcfg.optimizer, dtype=sdt),
        sync=init_sync_state(params,
                             compress="bf16" if tcfg.quantize_sync else None,
                             dtype=sdt),
        step=jnp.zeros((), jnp.int32),
    )


def add_dp_axis(state: TrainState, dp: int) -> TrainState:
    """Broadcast one replica's state to `dp` identical replicas (the paper's
    common x0)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (dp,) + x.shape), state)


def init_train_state(cfg: ModelConfig, tcfg: TrainConfig, tp: int, dp: int,
                     key: jax.Array, shardings=None) -> TrainState:
    """``shardings`` (a TrainState of NamedShardings): build the state in
    one jit whose outputs land sharded, so each device only ever holds its
    own replica instead of all ``dp`` broadcast onto the default device."""
    def build(k):
        return add_dp_axis(init_local_state(cfg, tcfg, tp, k), dp)

    if shardings is None:
        return build(key)
    return jax.jit(build, out_shardings=shardings)(key)


def squeeze_dp(state: TrainState) -> TrainState:
    return jax.tree.map(lambda x: x[0], state)


def unsqueeze_dp(state: TrainState) -> TrainState:
    return jax.tree.map(lambda x: x[None], state)
