"""Feed-forward layers: dense (gated / plain) MLP and expert-parallel MoE.

MoE (DESIGN.md §6): experts are sharded over the ``model`` axis (E/tp per
device).  Tokens are routed top-k with a capacity factor, packed into
(E, C) dispatch buffers, exchanged with ``all_to_all`` so each device
receives the tokens bound for ITS experts from every peer, run through the
local experts as one batched einsum, exchanged back and combined with the
router weights.  Dropped tokens (over capacity) contribute zero — the
residual stream carries them unchanged.

The router auxiliary load-balance loss (Switch-style f·p) is returned so the
trainer can add ``router_aux_coef``·aux to the task loss.  Router state drifts
between consistency syncs; the VAP bound caps that drift (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.common import ParamDef, ShardCtx, activation

MLP_IN = "mlp_in"     # checkpoint name of the w_in and w_gate outputs


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def mlp_defs(cfg: ModelConfig, tp: int, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    shard_ff = cfg.tp_strategy in ("head", "seq")    # d_ff shards in both
    sh1 = (None, "model") if shard_ff else (None, None)
    sh0 = ("model", None) if shard_ff else (None, None)
    defs = {
        "w_in": ParamDef((d, ff), sh1),
        "w_out": ParamDef((ff, d), sh0),
    }
    if cfg.gated_mlp:
        defs["w_gate"] = ParamDef((d, ff), sh1)
    return defs


def mlp_fwd(cfg: ModelConfig, ctx: ShardCtx, p: Dict, x: jnp.ndarray,
            sequence_parallel: bool = True) -> jnp.ndarray:
    """x: (b, s_loc, d) seq-sharded (head/seq strategies) or full (replicated).

    d_ff is column-sharded; with sequence-parallel residuals we all-gather
    the sequence in, reduce-scatter the partial output back.
    """
    shard_ff = cfg.tp_strategy in ("head", "seq") and ctx.model_axis is not None
    if shard_ff and sequence_parallel:
        xg = ctx.gather_seq(x, compress=cfg.compress_gathers)
    else:
        xg = x
    h = checkpoint_name(xg @ p["w_in"], MLP_IN)
    if cfg.gated_mlp:
        h = activation(cfg.act, h) * checkpoint_name(xg @ p["w_gate"], MLP_IN)
    else:
        h = activation(cfg.act, h)
    y = h @ p["w_out"]                                   # partial sums if sharded
    if shard_ff:
        y = ctx.scatter_seq(y) if sequence_parallel else ctx.psum_model(y)
    return y


def mlp_saved_bytes(cfg: ModelConfig, ctx: ShardCtx, batch: int, s_loc: int,
                    d_ff: int) -> int:
    """Bytes of what ``mlp_fwd`` names ``MLP_IN`` for the layer checkpoint
    over a sequence-parallel residual of (batch, s_loc, d): the w_in output
    and, gated, the w_gate output."""
    shard_ff = cfg.tp_strategy in ("head", "seq") and ctx.model_axis is not None
    s, ff = (s_loc * ctx.tp, d_ff // ctx.tp) if shard_ff else (s_loc, d_ff)
    n = 2 if cfg.gated_mlp else 1
    return batch * s * ff * n * jnp.dtype(cfg.dtype).itemsize


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def moe_defs(cfg: ModelConfig, tp: int) -> Dict[str, ParamDef]:
    d = cfg.d_model
    m: MoEConfig = cfg.moe
    de = m.d_expert
    defs = {
        "router": ParamDef((d, m.n_experts), (None, None), scale=0.1),
        "w_in": ParamDef((m.n_experts, d, de), ("model", None, None)),
        "w_out": ParamDef((m.n_experts, de, d), ("model", None, None)),
    }
    if cfg.gated_mlp:
        defs["w_gate"] = ParamDef((m.n_experts, d, de), ("model", None, None))
    if m.n_shared_experts:
        sh = {
            "w_in": ParamDef((d, m.d_shared), (None, "model")),
            "w_out": ParamDef((m.d_shared, d), ("model", None)),
        }
        if cfg.gated_mlp:
            sh["w_gate"] = ParamDef((d, m.d_shared), (None, "model"))
        defs["shared"] = sh
    return defs


def moe_fwd(cfg: ModelConfig, ctx: ShardCtx, p: Dict, x: jnp.ndarray,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (b, s_loc, d).  Returns (y, aux_loss)."""
    m: MoEConfig = cfg.moe
    b, s_loc, d = x.shape
    T = b * s_loc                                        # local tokens
    E, K = m.n_experts, m.top_k
    xt = x.reshape(T, d)

    # --- routing (f32 for numerics) ------------------------------------------
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)      # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Switch-style aux loss: E * sum_e f_e * p_e  (f = token fraction)
    token_frac = jnp.zeros(E).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
    prob_frac = probs.mean(0)
    aux = E * jnp.sum(token_frac * prob_frac)

    # --- dispatch packing -----------------------------------------------------
    C = max(1, int(np.ceil(T * K * m.capacity_factor / E)))
    flat_expert = expert_ids.reshape(-1)                 # (T*K,)
    flat_gate = gate_vals.reshape(-1)
    # rank of each (token, k) within its expert, in token order
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)      # (T*K, E)
    ranks = (jnp.cumsum(onehot, axis=0) - onehot)                 # exclusive
    rank_in_e = jnp.take_along_axis(ranks, flat_expert[:, None], 1)[:, 0]
    keep = rank_in_e < C
    slot = jnp.where(keep, flat_expert * C + rank_in_e, E * C)    # overflow bin

    buf = jnp.zeros((E * C + 1, d), xt.dtype).at[slot].add(
        jnp.repeat(xt, K, axis=0) * keep[:, None].astype(xt.dtype))
    buf = buf[:-1].reshape(E, C, d)

    # --- expert parallel exchange --------------------------------------------
    ep = ctx.model_axis is not None
    if ep:
        tp = ctx.tp
        # (E, C, d) -> (E/tp, C*tp, d): each device receives its experts'
        # tokens from every peer
        buf = ctx.all_to_all(buf, split_axis=0, concat_axis=1)
    e_loc = buf.shape[0]

    # --- local experts ---------------------------------------------------------
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"])
    if cfg.gated_mlp:
        h = activation(cfg.act, h) * jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    else:
        h = activation(cfg.act, h)
    out = jnp.einsum("ecf,efd->ecd", h, p["w_out"])

    if ep:
        out = ctx.all_to_all(out, split_axis=1, concat_axis=0)    # back to (E, C, d)

    # --- combine ----------------------------------------------------------------
    out_flat = jnp.concatenate([out.reshape(E * C, d),
                                jnp.zeros((1, d), out.dtype)], 0)
    gathered = out_flat[slot]                                     # (T*K, d)
    weighted = gathered * (flat_gate * keep).astype(gathered.dtype)[:, None]
    y = weighted.reshape(T, K, d).sum(1).reshape(b, s_loc, d)

    # --- shared experts ---------------------------------------------------------
    if m.n_shared_experts:
        sp = p["shared"]
        xg = ctx.gather_seq(x) if ep else x
        h = xg @ sp["w_in"]
        if cfg.gated_mlp:
            h = activation(cfg.act, h) * (xg @ sp["w_gate"])
        else:
            h = activation(cfg.act, h)
        ys = h @ sp["w_out"]
        ys = ctx.scatter_seq(ys) if ep else ys
        y = y + ys

    return y, aux.astype(jnp.float32)
