"""Causal flash attention on the TPU, forward and backward: the block-sparse
splash kernel that ships with jax
(``jax.experimental.pallas.ops.tpu.splash_attention``).

Each query block keeps its running max and denominator in f32 VMEM and
walks only the key blocks its mask reaches: blocks wholly above the
diagonal (or wholly left of a sliding window) are neither fetched nor
computed, in the forward and in both backward kernels (dq, and dk/dv).  No
score or probability block is written to HBM; the forward saves the
per-row logsumexp for the backward, and names it and its output
``ops.RESIDUALS`` for a layer checkpoint that keeps them.

Precision: q·kᵀ takes q and k in their own dtype (bf16 in the model) with
f32 accumulation; the softmax statistics are f32; the backward's products
take P and dS rounded to that dtype, with f32 accumulation.  The kernel
applies no 1/√dh, so it is folded into q here.

Block sizes follow the sequence length: the largest of :data:`BLOCKS` that
divides it, for every block of the forward and the backward.  The mask
information is built once per (length, heads, window, cap) and reused by
every call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as masks)

from repro.kernels.flash_attention.ops import RESIDUALS

BLOCKS = (512, 256, 128)


def block_size(s: int) -> Optional[int]:
    """The block for sequence length ``s``, or None where none divides it."""
    return next((b for b in BLOCKS if s % b == 0), None)


def takes(s: int, dh: int, dv: int) -> bool:
    """Whether the kernel runs this sequence length and these head dims."""
    return block_size(s) is not None and dh == dv and dh % 128 == 0


@functools.lru_cache(maxsize=None)
def _splash(s: int, heads: int, mqa: bool, window: Optional[int],
            cap: Optional[float], interpret: bool):
    blk = block_size(s)
    sizes = splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    one = (masks.CausalMask((s, s)) if window is None else
           masks.LocalMask((s, s), (window - 1, 0), offset=0))
    make = splash.make_splash_mqa if mqa else splash.make_splash_mha
    # concrete mask arrays even when first built inside a traced step
    with jax.ensure_compile_time_eval():
        return make(masks.MultiHeadMask([one] * heads), block_sizes=sizes,
                    attn_logits_soft_cap=cap, head_shards=1, q_seq_shards=1,
                    residual_checkpoint_name=RESIDUALS, interpret=interpret)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           window: Optional[int] = None,
                           cap: Optional[float] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Causal self-attention over positions 0..s-1 on both sides: query i
    attends to keys j <= i (and j > i - window).

    q: (b, s, kvh, G, dh) — G query heads per kv head; k, v: (b, s, kvh, dh).
    G == 1 runs the multi-head kernel over the kv heads; G > 1 the
    multi-query kernel per kv head, over its G query heads.
    """
    b, s, kvh, G, dh = q.shape
    if not takes(s, dh, v.shape[-1]) or k.shape[1] != s:
        raise ValueError(f"flash kernel does not take q {q.shape}, "
                         f"k {k.shape}, v {v.shape}")
    q = (q * (1.0 / np.sqrt(dh))).astype(q.dtype)
    kt = k.transpose(0, 2, 1, 3)                             # (b, kvh, s, dh)
    vt = v.transpose(0, 2, 1, 3)
    if G == 1:
        kern = _splash(s, kvh, False, window, cap, interpret)
        o = jax.vmap(kern)(q[:, :, :, 0].transpose(0, 2, 1, 3), kt, vt)
        return o.transpose(0, 2, 1, 3)[:, :, :, None]
    kern = _splash(s, G, True, window, cap, interpret)
    o = jax.vmap(jax.vmap(kern))(q.transpose(0, 2, 3, 1, 4), kt, vt)
    return o.transpose(0, 3, 1, 2, 4)                        # (b, s, kvh, G, dh)
