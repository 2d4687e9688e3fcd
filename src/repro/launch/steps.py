"""jit + shard_map step factories: train_step, prefill_step, serve_step.

``train_step`` is where the paper's technique lives in the TPU runtime:
grads → local optimizer update → ``core.sync.apply_and_sync`` (read-my-writes
apply + policy-triggered delta all-reduce over the data-parallel axes).

Each layer of the train step runs under one ``jax.named_scope`` (``embed``,
``blocks``, ``attention``, ``attention_core``, ``mlp``, ``lm_head`` in
``models/``; ``optimizer``, ``sync``, ``step_metrics`` here), so every
compiled op's ``op_name`` names its layer.  Scopes only label: the compiled
step is the same program with or without them.

The layer checkpoint's plan (``remat_plan``) is chosen while the step is
traced, from the local batch shape, the train state's bytes and the memory
the device reports: where they fit, the blocks' matmul outputs and the
flash kernel's residuals are kept for the backward instead of computed
again, and the step reports the bytes kept as ``remat_saved_bytes``.

Gradients of model-axis-replicated leaves (routers, norm scales, seq-TP
projections) are psum'd over the model axis so replicated copies stay
bitwise identical (Megatron rule); model-sharded leaves' grads are already
complete.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.core import policies as pol
from repro.core.sync import apply_and_sync
from repro.launch import mesh as mesh_lib
from repro.launch import specs as S
from repro.launch.state import TrainState, squeeze_dp, unsqueeze_dp
from repro.models import model as M
from repro.models.common import ParamDef, ShardCtx
from repro.optim import optimizer_update
from repro.optim.schedule import constant, linear_warmup

PyTree = Any


def make_ctx(mesh) -> ShardCtx:
    if mesh is None:
        return ShardCtx()
    return ShardCtx(model_axis="model", dp_axes=mesh_lib.dp_axes_of(mesh),
                    tp=mesh_lib.tp_size(mesh))


def _replicated_leaf_mask(cfg: ModelConfig, tp: int) -> PyTree:
    """True for leaves with no 'model' sharding (grads need a model psum)."""
    defs = M.model_defs(cfg, tp)
    return jax.tree.map(
        lambda d: "model" not in (d.shard or ()), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def remat_plan(cfg: ModelConfig, ctx: ShardCtx, batch_shape: Tuple[int, int],
               n_params: int, state_bytes: int, bytes_limit: Optional[int],
               ) -> Tuple[Any, int]:
    """The layer checkpoint's policy for a train step on one device, and the
    bytes it keeps: ``(M.SAVE_MATMULS, bytes)`` when what it saves over the
    local batch ``batch_shape`` (the layers under the checkpoint, as the
    config groups them) fits in half of what the device holds beyond the
    train state and one f32 copy of the ``n_params`` parameters (the
    gradients); else ``(None, 0)``, full recompute.  A device that reports
    no ``bytes_limit`` gets full recompute."""
    if bytes_limit is None:
        return None, 0
    saved = M.saved_bytes(cfg, ctx, *batch_shape)
    free = bytes_limit - state_bytes - 4 * n_params
    if 0 < saved <= free / 2:
        return M.SAVE_MATMULS, saved
    return None, 0


def device_bytes_limit(mesh) -> Optional[int]:
    """The memory one device of the step holds, as it reports it (None
    where it reports none, as the CPU does)."""
    dev = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
    return (dev.memory_stats() or {}).get("bytes_limit")


def _nbytes(tree) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh,
                    donate: bool = True, unroll: bool = False):
    """Returns jitted (state, batch) -> (state, metrics)."""
    ctx = make_ctx(mesh)
    policy = pol.from_spec(tcfg.consistency)
    lr_fn = linear_warmup(tcfg.lr, tcfg.warmup_steps, constant(tcfg.lr))
    opt_fn = optimizer_update(tcfg.optimizer)
    rep_mask = _replicated_leaf_mask(cfg, ctx.tp)
    all_axes = tuple(ctx.dp_axes) + ((ctx.model_axis,) if ctx.model_axis else ())
    bytes_limit = device_bytes_limit(mesh)

    def local_step(state: TrainState, batch: Dict):
        st = squeeze_dp(state)
        keep, saved = (remat_plan(
            cfg, ctx, batch["ids"].shape,
            sum(math.prod(x.shape) for x in jax.tree.leaves(st.params)),
            _nbytes(st), bytes_limit) if tcfg.remat else (None, 0))

        def loss_fn(p):
            return M.lm_loss(cfg, ctx, p, batch["ids"], batch["labels"],
                             extra_emb=batch.get("extra_emb"),
                             remat=tcfg.remat, remat_policy=keep,
                             unroll=unroll)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(st.params)
        if ctx.model_axis is not None:
            # Every model shard computes the (identical) loss redundantly, so
            # each local grad carries a tp× seed multiplicity; replicated
            # leaves additionally need their per-copy partials summed.
            # Universal rule (validated per-leaf against single-device grads
            # in tests/test_distributed.py): (psum if replicated else id)/tp.
            grads = jax.tree.map(
                lambda g, rep: (ctx.psum_model(g) if rep else g) / ctx.tp,
                grads, rep_mask)
        lr = lr_fn(st.step)
        with jax.named_scope("optimizer"):
            update, opt = opt_fn(grads, st.opt, lr,
                                 weight_decay=tcfg.weight_decay,
                                 params=st.params)
        with jax.named_scope("sync"):
            params, sync_state, synced = apply_and_sync(
                st.params, st.sync, update, policy, ctx.dp_axes,
                compress="bf16" if tcfg.quantize_sync else None,
                trigger_axes=all_axes)
            # in the scope: XLA roots the fused parameter and delta writes
            # at this reshape, and a fusion is named by its root
            new = unsqueeze_dp(TrainState(params=params, opt=opt,
                                          sync=sync_state, step=st.step + 1))
        with jax.named_scope("step_metrics"):
            out_metrics = {
                "loss": loss.astype(jnp.float32),
                "xent": metrics["xent"].astype(jnp.float32),
                "aux": metrics["aux"],
                "synced": synced.astype(jnp.float32),
                "grad_norm": jnp.sqrt(sum(
                    jnp.vdot(g, g).real for g in jax.tree.leaves(grads)
                )).astype(jnp.float32),
                "lr": lr,
                "remat_saved_bytes": jnp.float32(saved),
            }
            if all_axes:
                out_metrics = jax.tree.map(
                    lambda m: lax.pmean(m, all_axes), out_metrics)
        return new, out_metrics

    if mesh is None:
        return jax.jit(local_step, donate_argnums=(0,) if donate else ())

    dp_axes = mesh_lib.dp_axes_of(mesh)
    state_spec = S.resolve_tree(S.train_state_pspecs(cfg, tcfg, ctx.tp), dp_axes)
    bdp = dp_axes[0]
    batch_spec = {"ids": P(bdp, None), "labels": P(bdp, None)}
    if cfg.frontend is not None:
        batch_spec["extra_emb"] = P(bdp, None, None)
    metrics_spec = {k: P() for k in ("loss", "xent", "aux", "synced",
                                     "grad_norm", "lr", "remat_saved_bytes")}
    f = jax.shard_map(local_step, mesh=mesh,
                      in_specs=(state_spec, batch_spec),
                      out_specs=(state_spec, metrics_spec), check_vma=False)
    return jax.jit(f, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# Prefill / serve steps
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, mesh, shape: InputShape,
                      long_ctx: bool = False, unroll: bool = False):
    """(params, batch) -> (next_token (B,), caches)."""
    ctx = make_ctx(mesh)

    def local_prefill(params, batch):
        logits, caches = M.prefill(cfg, ctx, params, batch["ids"],
                                   capacity=shape.seq_len,
                                   extra_emb=batch.get("extra_emb"),
                                   long_ctx=long_ctx, unroll=unroll)
        nxt = M.sample_greedy(ctx, logits)
        return nxt, caches

    if mesh is None:
        return jax.jit(local_prefill)

    dp_axes = mesh_lib.dp_axes_of(mesh)
    dp_total = mesh_lib.dp_size(mesh)
    defs = M.model_defs(cfg, ctx.tp, long_ctx)
    from repro.models.common import pspec_tree
    param_spec = S.resolve_tree(pspec_tree(defs), dp_axes)
    babs, bspec = S.prefill_batch_specs(cfg, shape, dp_total)
    bspec = S.resolve_tree(bspec, dp_axes)
    cache_spec = S.resolve_tree(
        S.model_cache_pspecs(cfg, shape.global_batch, dp_total, long_ctx), dp_axes)
    bdp = bspec["ids"][0]
    out_specs = (P(bdp), cache_spec)
    f = jax.shard_map(local_prefill, mesh=mesh, in_specs=(param_spec, bspec),
                      out_specs=out_specs, check_vma=False)
    return jax.jit(f)


def make_serve_step(cfg: ModelConfig, mesh, shape: InputShape,
                    long_ctx: bool = False, unroll: bool = False):
    """(params, caches, batch{ids,pos}) -> (next_token (B,), caches)."""
    ctx = make_ctx(mesh)

    def local_serve(params, caches, batch):
        logits, new_caches = M.decode_step(cfg, ctx, params, batch["ids"],
                                           batch["pos"], caches,
                                           long_ctx=long_ctx, unroll=unroll)
        nxt = M.sample_greedy(ctx, logits)
        return nxt, new_caches

    if mesh is None:
        return jax.jit(local_serve)

    dp_axes = mesh_lib.dp_axes_of(mesh)
    dp_total = mesh_lib.dp_size(mesh)
    defs = M.model_defs(cfg, ctx.tp, long_ctx)
    from repro.models.common import pspec_tree
    param_spec = S.resolve_tree(pspec_tree(defs), dp_axes)
    babs, bspec = S.decode_batch_specs(cfg, shape, dp_total)
    bspec = S.resolve_tree(bspec, dp_axes)
    cache_spec = S.resolve_tree(
        S.model_cache_pspecs(cfg, shape.global_batch, dp_total, long_ctx), dp_axes)
    bdp = bspec["pos"][0] if len(bspec["pos"]) else None
    f = jax.shard_map(local_serve, mesh=mesh,
                      in_specs=(param_spec, cache_spec, bspec),
                      out_specs=(P(bdp), cache_spec), check_vma=False)
    return jax.jit(f, donate_argnums=(1,))    # caches are update-in-place
