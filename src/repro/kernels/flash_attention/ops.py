"""Public flash-attention op: dispatches Pallas kernel vs reference, and
decides which calls of the model's attention core the kernel takes."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import pallas_mode
from repro.kernels.flash_attention import ref

# The checkpoint name of the kernel's forward residuals (its output and the
# per-row logsumexp): a layer checkpoint whose policy saves this name keeps
# them, so the backward does not run the forward kernel again.
RESIDUALS = "flash_residuals"


def _is_arange(pos, n: int) -> bool:
    """``pos`` is known while tracing, and is 0..n-1."""
    if isinstance(pos, jax.core.Tracer) or np.shape(pos) != (n,):
        return False
    return bool(np.array_equal(np.asarray(pos), np.arange(n)))


def kernel_runs(s: int, dh: int, dv: int) -> bool:
    """Whether Pallas is on (or interpreted) and the kernel takes causal
    self-attention over ``s`` positions with these head dims."""
    if pallas_mode() not in ("on", "interpret"):
        return False
    from repro.kernels.flash_attention import kernel
    return kernel.takes(s, dh, dv)


def kernel_takes(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 q_pos, k_pos) -> bool:
    """Whether the kernel runs this call of the attention core: Pallas is
    on (or interpreted), the call is causal self-attention over positions
    0..s-1 on both sides (train and prefill without sequence sharding),
    and the kernel takes the head dims.  Decode (a cache is read, sq == 1),
    a sequence shard of q against gathered keys, and MLA's head dims are
    left to the caller's jnp core."""
    s = q.shape[1]
    return (k.shape[1] == s and kernel_runs(s, q.shape[-1], v.shape[-1])
            and _is_arange(q_pos, s) and _is_arange(k_pos, s))


@partial(jax.jit, static_argnames=("window", "cap"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    window: Optional[int] = None,
                    cap: Optional[float] = None) -> jnp.ndarray:
    """Causal self-attention over positions 0..s-1; differentiable.
    q: (b, s, kvh, G, dh); k, v: (b, s, kvh, dh)."""
    mode = pallas_mode()
    if mode in ("on", "interpret"):
        from repro.kernels.flash_attention import kernel
        return kernel.flash_attention_pallas(
            q, k, v, window=window, cap=cap, interpret=(mode == "interpret"))
    pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    return ref.attention(q, k, v, pos, pos, window=window, cap=cap)
