"""Public PS dense-block apply op: dispatches Pallas kernel vs numpy.

`scatter_add_inplace` is the runtime entry used by ServerShard._flush_updates
when PSRuntime(ps_kernels=True).  With pallas off it is exactly the seed
`np.add.at` path; with pallas on/interpret it routes through the kernel,
which accumulates duplicate rows in the same submission order, so the final
state stays bitwise equal to the simulator either way.  An f64 block runs
under `jax.enable_x64` so no precision is lost in transit (interpret mode
only: the TPU kernel refuses f64 with `kernel.DeviceDtypeError`).
"""
from __future__ import annotations

import contextlib
import functools

import numpy as np

from repro.kernels import pallas_mode


@functools.lru_cache(maxsize=1)
def _jitted_kernel():
    import jax

    from repro.kernels.ps_apply import kernel
    return jax.jit(kernel.scatter_add_pallas, static_argnames=("interpret",),
                   donate_argnums=(0,))


def scatter_add_inplace(dense: np.ndarray, rows: np.ndarray,
                        delta: np.ndarray) -> bool:
    """Accumulate delta[i] into dense[rows[i]] in place (np.add.at order).

    Returns True when the batch went through the Pallas kernel."""
    mode = pallas_mode()
    if mode == "off" or rows.shape[0] == 0:
        np.add.at(dense, rows, delta)
        return False
    import jax
    import jax.numpy as jnp

    n, r = rows.shape[0], dense.shape[0]
    # Pad N up to a power of two with entries the kernel skips (row R), so
    # jit retraces are bounded to O(log max-batch) shapes.
    npad = max(8, 1 << (n - 1).bit_length())
    rows_p = np.full(npad, r, np.int32)
    rows_p[:n] = rows
    delta_p = np.zeros((npad, dense.shape[1]), dense.dtype)
    delta_p[:n] = delta
    x64 = (jax.enable_x64(True) if dense.dtype == np.float64
           else contextlib.nullcontext())
    with x64:
        out = _jitted_kernel()(jnp.asarray(dense), jnp.asarray(rows_p),
                               jnp.asarray(delta_p),
                               interpret=(mode == "interpret"))
        dense[...] = np.asarray(out)
    return True
