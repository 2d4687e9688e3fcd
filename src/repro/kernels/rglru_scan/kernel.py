"""RG-LRU linear-recurrence Pallas kernel:  h_t = a_t ⊙ h_{t-1} + b_t.

Grid: (batch, width_blocks, seq_blocks) with the SEQUENCE axis innermost —
TPU grids iterate sequentially, so the running state lives in a VMEM scratch
accumulator that carries across seq blocks.  The width axis sits in vector
lanes (128-aligned blocks); the within-block time loop is a fori over
SEQ_BLK steps of pure VPU work.

The wrapper computes the RG-LRU gates (a_t, gated input) in jnp — they are
element-wise projections the surrounding matmuls already pay for — and the
kernel owns the sequential recurrence, which is the part XLA handles badly
(a log-depth associative scan materializes O(l) intermediates; the kernel
streams them through one VMEM tile).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

W_BLK = 128
SEQ_BLK = 128


def _kernel(a_ref, b_ref, init_ref, out_ref, h_ref):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        h_ref[...] = init_ref[...].astype(jnp.float32)

    def step(t, h):
        a_t = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        b_t = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a_t * h + b_t
        out_ref[0, pl.ds(t, 1), :] = h.astype(out_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, a_ref.shape[1], step, h_ref[...])


def linear_recurrence_pallas(a: jnp.ndarray, b: jnp.ndarray,
                             initial: Optional[jnp.ndarray] = None,
                             interpret: bool = False,
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """a, b: (batch, l, w).  Returns (h (batch, l, w), h_last (batch, w))."""
    bsz, l, w = a.shape
    pad_l = (-l) % SEQ_BLK
    pad_w = (-w) % W_BLK
    if pad_l or pad_w:
        # a=1, b=0 padding keeps the state constant through padded steps
        a = jnp.pad(a, ((0, 0), (0, pad_l), (0, pad_w)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_l), (0, pad_w)))
    lp, wp = l + pad_l, w + pad_w
    init = (jnp.zeros((bsz, wp), jnp.float32) if initial is None
            else jnp.pad(initial.astype(jnp.float32), ((0, 0), (0, pad_w))))

    grid = (bsz, wp // W_BLK, lp // SEQ_BLK)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, SEQ_BLK, W_BLK), lambda ib, iw, il: (ib, il, iw)),
            pl.BlockSpec((1, SEQ_BLK, W_BLK), lambda ib, iw, il: (ib, il, iw)),
            pl.BlockSpec((1, W_BLK), lambda ib, iw, il: (ib, iw)),
        ],
        out_specs=pl.BlockSpec((1, SEQ_BLK, W_BLK),
                               lambda ib, iw, il: (ib, il, iw)),
        out_shape=jax.ShapeDtypeStruct((bsz, lp, wp), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, W_BLK), jnp.float32)],
        interpret=interpret,
    )(a, b, init)
    # padded steps (a=1, b=0) leave the state unchanged, so the final padded
    # row equals the last valid state
    h_last = out[:, lp - 1, :w].astype(jnp.float32)
    return out[:, :l, :w], h_last


def rglru_pallas(x: jnp.ndarray, r_gate: jnp.ndarray, i_gate: jnp.ndarray,
                 a_param: jnp.ndarray, initial: Optional[jnp.ndarray] = None,
                 interpret: bool = False, c: float = 8.0,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    f32 = jnp.float32
    log_a = -c * jax.nn.softplus(a_param.astype(f32))[None, None, :] * r_gate.astype(f32)
    a = jnp.exp(log_a)
    a2 = jnp.exp(2.0 * log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * (i_gate.astype(f32) * x.astype(f32))
    h, h_last = linear_recurrence_pallas(a, gated, initial=initial,
                                         interpret=interpret)
    return h.astype(x.dtype), h_last
