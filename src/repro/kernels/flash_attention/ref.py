"""Pure-jnp oracle for the flash-attention kernel.

Dense masked attention in f32 — deliberately the simplest correct thing.
Matches the model-side chunked core (repro.models.attention.attention_core);
tests assert ref == chunked core == Pallas kernel, outputs and gradients.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              q_pos: jnp.ndarray, k_pos: jnp.ndarray,
              window: Optional[int] = None,
              cap: Optional[float] = None) -> jnp.ndarray:
    """q: (b, sq, kvh, G, dh); k, v: (b, skv, kvh, dh_{k,v});
    q_pos: (b, sq) or (sq,); k_pos: (b, skv) or (skv,)."""
    b, sq = q.shape[:2]
    skv = k.shape[1]
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (b, sq))
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None], (b, skv))
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cap is not None:
        s = jnp.tanh(s / cap) * cap
    m = k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    if window is not None:
        m &= k_pos[:, None, None, None, :] > (q_pos[:, None, None, :, None] - window)
    s = jnp.where(m, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(m.any(-1, keepdims=True), w, 0.0)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return o.astype(q.dtype)
