"""Partition specs for the train state, batches, and caches.

The "dp" marker stands for the data-parallel mesh axis and is resolved to
``'data'`` per mesh (``mesh.dp_axes_of``).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.launch import mesh as mesh_lib
from repro.launch.state import TrainState, init_local_state
from repro.core.sync import SyncState
from repro.models import model as M
from repro.models.common import ParamDef, pspec_tree
from repro.optim import OptState

PyTree = Any
DP = "dp"


def _resolve(spec, dp_axes: Tuple[str, ...]) -> P:
    parts = []
    for s in spec:
        if s == DP:
            parts.append(dp_axes if len(dp_axes) != 1 else dp_axes[0])
        else:
            parts.append(s)
    return P(*parts)


def resolve_tree(specs: PyTree, dp_axes: Tuple[str, ...]) -> PyTree:
    return jax.tree.map(lambda s: _resolve(s, dp_axes), specs,
                        is_leaf=lambda x: isinstance(x, P))


def shardings(specs: PyTree, mesh) -> PyTree:
    dp_axes = mesh_lib.dp_axes_of(mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, _resolve(s, dp_axes)),
                        specs, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Train-state specs
# ---------------------------------------------------------------------------


def train_state_pspecs(cfg: ModelConfig, tcfg: TrainConfig, tp: int) -> TrainState:
    """Spec tree mirroring TrainState (leaves: PartitionSpec with DP marker,
    leading dp axis on every leaf)."""
    defs = M.model_defs(cfg, tp)
    psp = pspec_tree(defs)
    abs_local = jax.eval_shape(
        lambda k: init_local_state(cfg, tcfg, tp, k), jax.random.key(0))

    def mirror(abs_sub: PyTree) -> PyTree:
        return jax.tree.map(
            lambda s, p: p if s.ndim > 0 else P(), abs_sub, psp)

    spec = TrainState(
        params=psp,
        opt=OptState(mu=mirror(abs_local.opt.mu), nu=mirror(abs_local.opt.nu),
                     count=P()),
        sync=SyncState(delta=mirror(abs_local.sync.delta),
                       residual=mirror(abs_local.sync.residual),
                       steps_since_sync=P(), sync_count=P(),
                       max_update_mag=P(), max_update_l2=P()),
        step=P(),
    )
    return jax.tree.map(lambda s: P(DP, *s), spec,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------


def _batch_dp(B: int, dp_total: int) -> Optional[str]:
    """Shard batch over dp only when divisible (a batch of 1 is not)."""
    return DP if dp_total > 1 and B % dp_total == 0 else None


def prefill_batch_specs(cfg: ModelConfig, shape: InputShape, dp_total: int,
                        ) -> Tuple[Dict, Dict]:
    B, S = shape.global_batch, shape.seq_len
    bspec = _batch_dp(B, dp_total)
    abst = {"ids": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    spec = {"ids": P(bspec, None)}
    if cfg.frontend is not None:
        abst["extra_emb"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend.n_embeds, cfg.d_model), jnp.dtype(cfg.dtype))
        spec["extra_emb"] = P(bspec, None, None)
    return abst, spec


def decode_batch_specs(cfg: ModelConfig, shape: InputShape, dp_total: int,
                       ) -> Tuple[Dict, Dict]:
    B = shape.global_batch
    bspec = _batch_dp(B, dp_total)
    abst = {"ids": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((B,), jnp.int32)}
    spec = {"ids": P(bspec, None), "pos": P(bspec)}
    return abst, spec


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------


def _attn_cache_pspec(cfg: ModelConfig, bspec) -> Dict[str, P]:
    if cfg.mla is not None:
        return {"latent": P(bspec, None, None), "k_rope": P(bspec, None, None),
                "pos": P(bspec, None)}
    if cfg.tp_strategy == "head":
        return {"k": P(bspec, None, "model", None),
                "v": P(bspec, None, "model", None), "pos": P(bspec, None)}
    if cfg.tp_strategy == "seq":
        return {"k": P(bspec, "model", None, None),
                "v": P(bspec, "model", None, None), "pos": P(bspec, "model")}
    return {"k": P(bspec, None, None, None), "v": P(bspec, None, None, None),
            "pos": P(bspec, None)}


def _rec_cache_pspec(cfg: ModelConfig, bspec) -> Dict[str, P]:
    if cfg.recurrent.kind == "rglru":
        if cfg.tp_strategy == "head":
            return {"h": P(bspec, "model"), "conv": P(bspec, None, "model")}
        return {"h": P(bspec, None), "conv": P(bspec, None, None)}
    return {"h": P(bspec, None, None, None), "conv": P(bspec, None, None)}


def model_cache_pspecs(cfg: ModelConfig, B: int, dp_total: int,
                       long_ctx: bool = False) -> Dict:
    bspec = _batch_dp(B, dp_total)
    metas = M.layer_metas(cfg, long_ctx)
    prefix, unit, n_units, tail = M.group_layers(cfg, metas)

    def block(meta):
        return (_attn_cache_pspec(cfg, bspec) if meta.kind == "attn"
                else _rec_cache_pspec(cfg, bspec))

    def stack(spec):
        return jax.tree.map(lambda s: P(None, *s), spec,
                            is_leaf=lambda x: isinstance(x, P))

    return {"prefix": [block(m) for m in prefix],
            "scan": [stack(block(m)) for m in unit],
            "tail": [block(m) for m in tail]}
