"""Pure-jnp oracle for the Mamba-2 SSD chunked scan (arXiv:2405.21060 §6).

State-space duality: within a chunk the recurrence is computed as a masked
quadratic attention-like product; across chunks states are passed through a
sequential decay recurrence.

Memory discipline (this path runs wherever the Pallas kernel does not):
  * B/C stay at GROUP granularity — never `repeat`ed to heads;
  * bulk tensors stay in the input dtype (bf16 in production), only the
    decay/cumsum bookkeeping is f32;
  * einsums are pairwise with the (b, h, nc, cs, cs) score block as the
    largest intermediate (the Pallas kernel tiles this same structure).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def segsum(x: jnp.ndarray) -> jnp.ndarray:
    """x: (..., l) -> (..., l, l) with out[m, s] = sum_{i=s+1..m} x_i (s<=m),
    -inf above the diagonal."""
    l = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, chunk: int,
                initial_state: Optional[jnp.ndarray] = None,
                max_score_bytes: int = 128 * 2**20,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD scan.

    x:  (b, l, h, p)     inputs per head
    dt: (b, l, h)        discretization steps (post-softplus, f32)
    A:  (h,)             negative decay rates
    B:  (b, l, g, n)     input projections at group granularity (h % g == 0)
    C:  (b, l, g, n)     output projections
    Returns y (b, l, h, p) and final_state (b, h, p, n) (f32).

    When the (b, h, nc, cs, cs) score block would exceed ``max_score_bytes``,
    the batch is processed in slices with ``lax.map`` (the Pallas kernel
    tiles the same structure in VMEM; this keeps the jnp path's compiled
    footprint comparable).
    """
    b, l, h, p = x.shape
    score_bytes = b * h * l * chunk * x.dtype.itemsize
    if score_bytes > max_score_bytes and b > 1:
        bb = max(1, int(b * max_score_bytes / score_bytes))
        while b % bb:
            bb -= 1
        if bb < b:
            xs_ = x.reshape(b // bb, bb, l, h, p)
            dts = dt.reshape(b // bb, bb, l, h)
            Bs = B.reshape(b // bb, bb, l, *B.shape[2:])
            Cs = C.reshape(b // bb, bb, l, *C.shape[2:])
            inits = (None if initial_state is None
                     else initial_state.reshape(b // bb, bb, *initial_state.shape[1:]))

            def fn(args):
                if initial_state is None:
                    xb, db, Bb, Cb = args
                    return ssd_chunked(xb, db, A, Bb, Cb, chunk,
                                       max_score_bytes=2**62)
                xb, db, Bb, Cb, ib = args
                return ssd_chunked(xb, db, A, Bb, Cb, chunk, initial_state=ib,
                                   max_score_bytes=2**62)

            args = ((xs_, dts, Bs, Cs) if initial_state is None
                    else (xs_, dts, Bs, Cs, inits))
            ys, sts = lax.map(fn, args)
            return (ys.reshape(b, l, h, p),
                    sts.reshape(b, h, p, sts.shape[-1]))
    g = B.shape[2]
    hg = h // g
    n = B.shape[-1]
    dt_c = x.dtype        # bulk compute dtype
    f32 = jnp.float32

    l_orig = l
    if l % chunk:
        # zero-pad: dt=0 ⇒ decay=1 and zero input, so padded steps are no-ops
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        l = l + pad
    nc, cs_ = l // chunk, chunk

    xdt = (x.astype(f32) * dt.astype(f32)[..., None]).astype(dt_c)   # dt·x
    dA = dt.astype(f32) * A.astype(f32)[None, None, :]               # (b, l, h)

    xc = xdt.reshape(b, nc, cs_, h, p)
    Bc = B.astype(dt_c).reshape(b, nc, cs_, g, n)
    Cc = C.astype(dt_c).reshape(b, nc, cs_, g, n)
    dAc = dA.reshape(b, nc, cs_, h).transpose(0, 3, 1, 2)            # (b, h, nc, cs)
    A_cum = jnp.cumsum(dAc, axis=-1)                                 # f32

    # 1. intra-chunk (quadratic): group-level CBᵀ, head-level decay mask
    L = jnp.exp(segsum(dAc)).astype(dt_c)                            # (b, h, nc, m, s)
    cb = jnp.einsum("bcmgn,bcsgn->bgcms", Cc, Bc,
                    preferred_element_type=f32).astype(dt_c)         # (b, g, nc, m, s)
    scores = (cb.reshape(b, g, 1, nc, cs_, cs_)
              * L.reshape(b, g, hg, nc, cs_, cs_)).reshape(b, h, nc, cs_, cs_)
    Y_diag = jnp.einsum("bhcms,bcshp->bcmhp", scores, xc,
                        preferred_element_type=f32).astype(dt_c)

    # 2. per-chunk output states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum).astype(dt_c)     # (b, h, nc, cs)
    xdec = (xc * decay_states.transpose(0, 2, 3, 1)[..., None])      # (b,nc,cs,h,p)
    xdec_g = xdec.reshape(b, nc, cs_, g, hg, p)
    states = jnp.einsum("bcsgn,bcsghp->bcghpn", Bc, xdec_g,
                        preferred_element_type=f32)                   # f32
    states = states.reshape(b, nc, h, p, n)

    # 3. inter-chunk recurrence (sequential over chunks, f32 state)
    chunk_decay = jnp.exp(A_cum[..., -1])                            # (b, h, nc) f32
    init = (jnp.zeros((b, h, p, n), f32) if initial_state is None
            else initial_state.astype(f32))

    def step(carry, inp):
        s_c, decay_c = inp                                           # (b,h,p,n), (b,h)
        new = s_c + decay_c[..., None, None] * carry
        return new, carry                                            # emit state ENTERING chunk

    final_state, states_prev = lax.scan(
        step, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(2, 0, 1)))
    states_prev = states_prev.transpose(1, 0, 2, 3, 4).astype(dt_c)  # (b, nc, h, p, n)

    # 4. inter-chunk contribution to outputs
    state_decay = jnp.exp(A_cum).astype(dt_c)                        # (b, h, nc, cs)
    Ch = Cc.reshape(b, nc, cs_, g, 1, n)
    sp = states_prev.reshape(b, nc, g, hg, p, n)
    Y_off = jnp.einsum("bcmgon,bcghpn->bcmghp", Ch, sp,
                       preferred_element_type=f32).reshape(b, nc, cs_, h, p)
    Y_off = (Y_off * state_decay.transpose(0, 2, 3, 1)[..., None]).astype(dt_c)

    y = (Y_diag + Y_off).reshape(b, l, h, p)[:, :l_orig]
    return y.astype(x.dtype), final_state


def ssd_step(h_state: jnp.ndarray, x_t: jnp.ndarray, dt_t: jnp.ndarray,
             A: jnp.ndarray, B_t: jnp.ndarray, C_t: jnp.ndarray,
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single decode step.

    h_state: (b, h, p, n); x_t: (b, h, p); dt_t: (b, h); B_t/C_t: (b, h, n)
    Returns (y_t (b, h, p), new_state).
    """
    f32 = jnp.float32
    dA = jnp.exp(dt_t.astype(f32) * A.astype(f32)[None, :])          # (b, h)
    inp = (dt_t.astype(f32)[..., None] * x_t.astype(f32))            # (b, h, p)
    new = (h_state.astype(f32) * dA[..., None, None]
           + inp[..., None] * B_t.astype(f32)[..., None, :])
    y = jnp.einsum("bhpn,bhn->bhp", new, C_t.astype(f32))
    return y.astype(x_t.dtype), new
