"""TPU/SPMD adaptation of the consistency models (the SPMD layer).

On a lockstep SPMD mesh, each data-parallel replica keeps its own *drifting*
copy of the parameters plus an accumulated unsynchronized delta ``δ``.
Updates apply locally first (read-my-writes); a jax.lax Consistency
Controller decides per step whether the delta all-reduce runs:

    BSP   : every step.
    SSP/CAP/ESSP(s): every s-th step (staleness ≤ s by construction; in
            lockstep SPMD the push-early vs push-at-clock distinction AND
            ESSP's eager server push both collapse — every sync epoch is a
            full exchange, so the server can't be "ahead" of it;
            arXiv:1410.8043).
    VAP(v): when any replica's ‖δ‖∞ would exceed v_thr — one scalar pmax per
            step, the TPU analogue of the paper's per-worker blocking.
    CVAP  : clock OR value trigger.
    elastic(B): when any replica's whole-tree ‖δ‖₂ would exceed B — the
            elastic-consistency bound (arXiv:2001.05918) as a single scalar
            pmax trigger, so post-step ‖δ‖₂ ≤ max(‖u‖₂, B) by construction.

The sync itself is ``params ← params + (Σ_replicas δ) − δ`` — the associative
and commutative update rule of §2, so FIFO/ordering concerns vanish and the
result equals the paper's "all updates visible" state.

Beyond the paper, ``compress="bf16"`` all-reduces the deltas in bf16 with an
fp32 error-feedback residual (the VAP bound caps |δ| and hence the
quantization error).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.policies import INF, Policy

PyTree = Any


# ---------------------------------------------------------------------------
# Sync state (a pytree carried in TrainState)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SyncState:
    delta: PyTree                  # accumulated unsynchronized updates
    residual: PyTree               # error-feedback residual (compress mode)
    steps_since_sync: jnp.ndarray  # i32 scalar
    sync_count: jnp.ndarray        # i32 scalar — total sync epochs so far
    max_update_mag: jnp.ndarray    # f32 scalar — running max ‖u‖∞ (bound check)
    max_update_l2: jnp.ndarray     # f32 scalar — running max ‖u‖₂ (elastic)


def init_sync_state(params: PyTree, compress: Optional[str] = None,
                    dtype=None) -> SyncState:
    """dtype: storage dtype of the delta accumulator (bf16 halves both the
    resident bytes and the sync all-reduce volume; the VAP bound caps |δ|,
    so bf16's relative precision is adequate)."""
    zeros = lambda: jax.tree.map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), params)
    none_tree = jax.tree.map(lambda x: jnp.zeros((), x.dtype), params)
    return SyncState(
        delta=zeros(),
        residual=zeros() if compress else none_tree,
        steps_since_sync=jnp.zeros((), jnp.int32),
        sync_count=jnp.zeros((), jnp.int32),
        max_update_mag=jnp.zeros((), jnp.float32),
        max_update_l2=jnp.zeros((), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_zeros(t: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, t)


def tree_max_abs(t: PyTree) -> jnp.ndarray:
    """max over all leaves of ‖leaf‖∞ (f32 scalar)."""
    leaves = [jnp.max(jnp.abs(x)).astype(jnp.float32) for x in jax.tree.leaves(t)]
    return jnp.max(jnp.stack(leaves)) if leaves else jnp.zeros((), jnp.float32)


def tree_l2_norm(t: PyTree) -> jnp.ndarray:
    """L2 norm over the whole tree, ‖t‖₂ (f32 scalar) — the elastic bound's
    aggregate, matching the simulator's whole-accumulator norm."""
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(t)]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _psum_tree(t: PyTree, axes: Sequence[str], compress: Optional[str]) -> PyTree:
    axes = tuple(axes)
    if compress == "bf16":
        return jax.tree.map(
            lambda x: lax.psum(x.astype(jnp.bfloat16), axes).astype(x.dtype), t)
    return jax.tree.map(lambda x: lax.psum(x, axes), t)


# ---------------------------------------------------------------------------
# Triggers — the jax.lax Consistency Controller
# ---------------------------------------------------------------------------


def sync_trigger(policy: Policy, sync_state: SyncState, new_delta: PyTree,
                 dp_axes: Sequence[str],
                 trigger_axes: Optional[Sequence[str]] = None) -> jnp.ndarray:
    """Mesh-uniform boolean: must this step run the delta all-reduce?

    The value trigger is made uniform with a pmax over ``trigger_axes`` —
    the data-parallel axes PLUS the model axis when parameters are
    tensor-sharded (each model shard only sees its slice's ‖δ‖∞; all shards
    must take the same cond branch).  The paper's per-worker block becomes a
    mesh-wide sync epoch — conservative, so the VAP invariant still holds:
    a replica syncs no later than its own bound would make it.
    """
    axes = tuple(trigger_axes) if trigger_axes is not None else tuple(dp_axes)
    trig = jnp.zeros((), jnp.bool_)
    if policy.clock_bounded:
        s = max(policy.staleness, 0)
        trig = trig | (sync_state.steps_since_sync + 1 >= s + 1)
    if policy.value_bounded and policy.value_bound != INF:
        local = tree_max_abs(new_delta)
        glob = lax.pmax(local, axes) if axes else local
        trig = trig | (glob > policy.value_bound)
    if policy.norm_bounded:
        # elastic: one scalar — would any replica's whole-tree ‖δ‖₂ exceed
        # the bound?  Same conservative mesh-wide uniformity as VAP.
        local = tree_l2_norm(new_delta)
        glob = lax.pmax(local, axes) if axes else local
        trig = trig | (glob > policy.value_bound)
    if not (policy.clock_bounded or policy.value_bounded
            or policy.norm_bounded):
        trig = jnp.ones((), jnp.bool_)     # degenerate: stay synchronous
    return trig


# ---------------------------------------------------------------------------
# The sync step
# ---------------------------------------------------------------------------


def apply_and_sync(
    params: PyTree,
    sync_state: SyncState,
    update: PyTree,
    policy: Policy,
    dp_axes: Sequence[str],
    compress: Optional[str] = None,
    trigger_axes: Optional[Sequence[str]] = None,
) -> Tuple[PyTree, SyncState, jnp.ndarray]:
    """Apply a local optimizer update, then maybe synchronize replicas.

    Returns (params, sync_state, synced: bool scalar).

    * read-my-writes: ``params`` immediately include ``update``.
    * on sync: params ← params + (psum(δ) − δ); δ ← 0.  Because updates are
      additive and commutative this equals the fully-synchronized state.
    """
    dp_axes = tuple(dp_axes)
    params = tree_add(params, update)
    # keep the accumulator's storage dtype (bf16 under state_dtype=bfloat16)
    new_delta = jax.tree.map(lambda d, u: (d + u).astype(d.dtype),
                             sync_state.delta, update)
    umag = jnp.maximum(sync_state.max_update_mag, tree_max_abs(update))
    ul2 = jnp.maximum(sync_state.max_update_l2, tree_l2_norm(update))
    trig = sync_trigger(policy, sync_state, new_delta, dp_axes,
                        trigger_axes=trigger_axes)

    if not dp_axes:
        # single replica: every "sync" is a no-op but the clock still ticks
        new_state = SyncState(
            delta=jax.tree.map(lambda d: jnp.where(trig, jnp.zeros_like(d), d), new_delta),
            residual=sync_state.residual,
            steps_since_sync=jnp.where(trig, 0, sync_state.steps_since_sync + 1).astype(jnp.int32),
            sync_count=(sync_state.sync_count + trig.astype(jnp.int32)),
            max_update_mag=umag,
            max_update_l2=ul2,
        )
        return params, new_state, trig

    def compressed_send(d, r):
        """Quantize δ+r to bf16, keep the error as the next residual."""
        send = tree_add(d, r)
        comp = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(x.dtype), send)
        return comp, tree_sub(send, comp)

    def do_sync(operand):
        p, d, r = operand
        if compress:
            d_send, r = compressed_send(d, r)
        else:
            d_send = d
        tot = _psum_tree(d_send, dp_axes, compress)
        p = tree_add(p, tree_sub(tot, d_send))
        return p, tree_zeros(d), r

    params, delta_out, residual = lax.cond(
        trig, do_sync, lambda operand: operand,
        (params, new_delta, sync_state.residual))

    new_state = SyncState(
        delta=delta_out,
        residual=residual,
        steps_since_sync=jnp.where(trig, 0, sync_state.steps_since_sync + 1).astype(jnp.int32),
        sync_count=sync_state.sync_count + trig.astype(jnp.int32),
        max_update_mag=umag,
        max_update_l2=ul2,
    )
    return params, new_state, trig


def force_sync(params: PyTree, sync_state: SyncState,
               dp_axes: Sequence[str]) -> Tuple[PyTree, SyncState]:
    """Unconditional sync (used at checkpoint/eval boundaries)."""
    dp_axes = tuple(dp_axes)
    if dp_axes:
        tot = _psum_tree(sync_state.delta, dp_axes, None)
        params = tree_add(params, tree_sub(tot, sync_state.delta))
    new_state = dataclasses.replace(
        sync_state,
        delta=tree_zeros(sync_state.delta),
        steps_since_sync=jnp.zeros((), jnp.int32),
        sync_count=sync_state.sync_count + 1,
    )
    return params, new_state


def vap_invariant_ok(policy: Policy, sync_state: SyncState) -> jnp.ndarray:
    """‖δ‖∞ ≤ max(u_max, v_thr) — checked by tests after every step."""
    if not policy.value_bounded:
        return jnp.ones((), jnp.bool_)
    bound = jnp.maximum(sync_state.max_update_mag, policy.value_bound)
    return tree_max_abs(sync_state.delta) <= bound + 1e-6


def elastic_invariant_ok(policy: Policy, sync_state: SyncState) -> jnp.ndarray:
    """‖δ‖₂ ≤ max(‖u‖₂_max, B) — checked by tests after every step."""
    if not policy.norm_bounded:
        return jnp.ones((), jnp.bool_)
    bound = jnp.maximum(sync_state.max_update_l2, policy.value_bound)
    return tree_l2_norm(sync_state.delta) <= bound + 1e-6
