"""Shared model machinery: shard context, norms, RoPE, parameter specs.

Parameter handling has one source of truth: :func:`param_defs` builders
return a pytree of :class:`ParamDef` (shape + sharded dims + init rule).
From it we derive
  * concrete arrays            (``instantiate``)
  * ``PartitionSpec``s         (``pspec``)         — for pjit/shard_map

Model forward code is written against LOCAL (per-device) shapes inside
``shard_map``; :class:`ShardCtx` carries the mesh axis names and the
collective helpers, all of which degrade to no-ops at tp=1 so the same code
runs single-device in the CPU tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

PyTree = Any


# ---------------------------------------------------------------------------
# Shard context
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardCtx:
    """Mesh axes visible to model code (inside shard_map)."""

    model_axis: Optional[str] = None     # tensor-parallel axis name
    dp_axes: Tuple[str, ...] = ()        # data-parallel axes (consistency sync)
    tp: int = 1                          # size of the model axis

    # ---- collectives (no-ops at tp == 1) ------------------------------------
    def index(self) -> jnp.ndarray:
        if self.model_axis is None:
            return jnp.zeros((), jnp.int32)
        return lax.axis_index(self.model_axis)

    def gather_seq(self, x: jnp.ndarray, axis: int = 1,
                   compress: bool = False) -> jnp.ndarray:
        """(…, s/tp, …) -> (…, s, …): sequence-parallel all-gather.

        compress=True sends int8 with a per-shard scale (halves the gather
        volume vs bf16 at ~0.4% activation error — EXPERIMENTS §Perf)."""
        if self.model_axis is None:
            return x
        if not compress or not jnp.issubdtype(x.dtype, jnp.floating):
            return lax.all_gather(x, self.model_axis, axis=axis, tiled=True)
        scale = (jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0 + 1e-12)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
        qg = lax.all_gather(q, self.model_axis, axis=axis, tiled=True)
        sg = lax.all_gather(scale[None], self.model_axis)        # (tp,)
        # de-quantize block-wise: axis is a concat of tp per-shard blocks
        shape = qg.shape
        loc = shape[axis] // self.tp
        blocked = qg.reshape(shape[:axis] + (self.tp, loc) + shape[axis + 1:])
        s_shape = (1,) * axis + (self.tp, 1) + (1,) * (len(shape) - axis - 1)
        out = blocked.astype(jnp.float32) * sg.reshape(s_shape)
        return out.reshape(shape).astype(x.dtype)

    def scatter_seq(self, x: jnp.ndarray, axis: int = 1) -> jnp.ndarray:
        """(…, s, …) partial-sums -> (…, s/tp, …): reduce-scatter."""
        if self.model_axis is None:
            return x
        return lax.psum_scatter(x, self.model_axis, scatter_dimension=axis,
                                tiled=True)

    def psum_model(self, x):
        if self.model_axis is None:
            return x
        return lax.psum(x, self.model_axis)

    def pmax_model(self, x):
        if self.model_axis is None:
            return x
        return lax.pmax(x, self.model_axis)

    def all_to_all(self, x: jnp.ndarray, split_axis: int, concat_axis: int) -> jnp.ndarray:
        if self.model_axis is None:
            return x
        return lax.all_to_all(x, self.model_axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: global shape + sharding + init rule."""

    shape: Tuple[int, ...]
    shard: Tuple[Optional[str], ...] = ()    # per-dim mesh axis (or None)
    init: str = "fan_in"                     # fan_in | zeros | ones | embed | kv_dup
    scale: float = 1.0
    dtype: Any = jnp.float32
    # kv_dup: generate (d, base_heads, hd) and repeat heads `rep`× -> shape
    kv_base_heads: int = 0
    kv_rep: int = 1

    def __post_init__(self):
        if self.shard and len(self.shard) != len(self.shape):
            raise ValueError(f"shard {self.shard} vs shape {self.shape}")

    def instantiate(self, key: jax.Array) -> jnp.ndarray:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "embed":
            return (jax.random.normal(key, self.shape, self.dtype)
                    * jnp.asarray(self.scale, self.dtype))
        if self.init == "fan_in":
            # fan-in = the matmul input dim (second-to-last; robust to
            # scan-stacked leading dims)
            fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
            std = self.scale / np.sqrt(max(fan_in, 1))
            return (jax.random.truncated_normal(key, -3, 3, self.shape, self.dtype)
                    * jnp.asarray(std, self.dtype))
        if self.init == "kv_dup":
            # duplicated-KV layout: identical weights for replicated kv heads.
            # shape is (*lead, d, heads*hd); duplication happens on the head
            # axis of the LAST dim (robust to scan-stacked leading dims).
            lead, d, rest = self.shape[:-2], self.shape[-2], self.shape[-1]
            hd = rest // (self.kv_base_heads * self.kv_rep)
            std = self.scale / np.sqrt(d)
            base = (jax.random.truncated_normal(
                key, -3, 3, lead + (d, self.kv_base_heads, hd), self.dtype)
                * jnp.asarray(std, self.dtype))
            full = jnp.repeat(base, self.kv_rep, axis=-2)
            return full.reshape(self.shape)
        raise ValueError(f"unknown init {self.init!r}")

    def pspec(self) -> P:
        if not self.shard:
            return P()
        return P(*self.shard)


def instantiate_tree(defs: PyTree, key: jax.Array) -> PyTree:
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    vals = [d.instantiate(k) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def pspec_tree(defs: PyTree) -> PyTree:
    return jax.tree.map(lambda d: d.pspec(), defs,
                        is_leaf=lambda x: isinstance(x, ParamDef))


def local_view(defs: PyTree, tp: int) -> PyTree:
    """ShapeDtypeStructs of the per-device (local) shapes at tensor-parallel
    degree tp — used by tests to sanity-check the forward code's layout."""

    def loc(d: ParamDef) -> jax.ShapeDtypeStruct:
        shape = list(d.shape)
        for i, ax in enumerate(d.shard or ()):
            if ax == "model":
                if shape[i] % tp:
                    raise ValueError(f"dim {i} of {d.shape} not divisible by tp={tp}")
                shape[i] //= tp
        return jax.ShapeDtypeStruct(tuple(shape), d.dtype)

    return jax.tree.map(loc, defs, is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jnp.ndarray, scale: Optional[jnp.ndarray], eps: float = 1e-6,
            gemma_style: bool = False) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    if scale is not None:
        s = scale.astype(jnp.float32)
        x = x * (1.0 + s) if gemma_style else x * s
    return x.astype(dt)


def layernorm(x: jnp.ndarray, scale: Optional[jnp.ndarray],
              bias: Optional[jnp.ndarray], eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * lax.rsqrt(var + eps)
    if scale is not None:
        x = x * scale.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return x.astype(dt)


def apply_norm(kind: str, x: jnp.ndarray, params: Optional[Dict]) -> jnp.ndarray:
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    if kind == "gemma_rmsnorm":
        return rmsnorm(x, params["scale"], gemma_style=True)
    if kind == "layernorm":
        return layernorm(x, params["scale"], params["bias"])
    if kind == "nonparam_ln":
        return layernorm(x, None, None)
    raise ValueError(f"unknown norm {kind!r}")


def norm_defs(kind: str, d: int) -> Optional[Dict[str, ParamDef]]:
    if kind in ("rmsnorm",):
        return {"scale": ParamDef((d,), (None,), init="ones")}
    if kind == "gemma_rmsnorm":
        return {"scale": ParamDef((d,), (None,), init="zeros")}   # (1+scale)
    if kind == "layernorm":
        return {"scale": ParamDef((d,), (None,), init="ones"),
                "bias": ParamDef((d,), (None,), init="zeros")}
    if kind == "nonparam_ln":
        return {}   # empty dict keeps the pytree structure homogeneous
    raise ValueError(f"unknown norm {kind!r}")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (b, s, h, hd); positions: (b, s) or (s,) int32."""
    dt = x.dtype
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta)                       # (hd/2,)
    pos = positions.astype(jnp.float32)
    ang = pos[..., None] * inv                              # (b, s, hd/2) / (s, hd/2)
    if ang.ndim == 2:                                       # (s, hd/2)
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Activations / misc
# ---------------------------------------------------------------------------


def activation(kind: str, x: jnp.ndarray) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {kind!r}")


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def kv_eff_heads(n_kv: int, tp: int) -> Tuple[int, int]:
    """(effective kv heads after duplication, repeat factor)."""
    if n_kv >= tp:
        if n_kv % tp:
            raise ValueError(f"n_kv={n_kv} not divisible by tp={tp}")
        return n_kv, 1
    if tp % n_kv:
        raise ValueError(f"tp={tp} not a multiple of n_kv={n_kv}")
    return tp, tp // n_kv
