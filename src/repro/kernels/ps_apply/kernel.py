"""Segment scatter-add Pallas kernel for the PS dense-block apply.

The shard hot path lands a coalesced batch of (rows, delta) updates into a
dense block with `np.add.at(dense, rows, delta)`.  This kernel performs the
same accumulation on-chip without staging the block in VMEM: the block stays
in HBM (aliased in and out), and for each batch entry in submission order
the kernel DMAs row rows[i] into a one-row VMEM buffer, adds delta row i and
DMAs it back, waiting on each copy.  Every add therefore sees the previous
one's result — the same order `np.add.at` uses — so duplicate rows
accumulate bitwise-identically to the numpy path, and VMEM holds one row
plus one delta tile whatever the block size.

Conventions:
  * rows may contain the sentinel index R (== dense.shape[0]); such entries
    are skipped, so callers pad batches with it.
  * The grid walks the batch in ROW_BLK-entry delta tiles in VMEM; it must
    run sequentially, which "arbitrary" semantics guarantee.  The row ids
    sit whole in SMEM (1 MiB on v5e), so batches longer than SMEM_ROWS are
    applied as consecutive calls, which keeps submission order.
  * Mosaic has no 64-bit floats: a float64 block raises DeviceDtypeError
    unless the kernel runs in the interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128
ROW_BLK = 512
SMEM_ROWS = 1 << 15


class DeviceDtypeError(TypeError):
    """The block dtype cannot be compiled for the TPU (no f64 in Mosaic)."""


def _kernel(rows_ref, delta_ref, dense_hbm, out_hbm, row_buf, sem):
    del dense_hbm                       # aliased to out_hbm
    n_rows = out_hbm.shape[0]
    base = pl.program_id(0) * delta_ref.shape[0]

    def body(i, carry):
        r = rows_ref[base + i]

        @pl.when(r < n_rows)
        def _add():
            load = pltpu.make_async_copy(out_hbm.at[pl.ds(r, 1)], row_buf, sem)
            load.start()
            load.wait()
            row_buf[...] = row_buf[...] + delta_ref[pl.ds(i, 1), :]
            store = pltpu.make_async_copy(row_buf, out_hbm.at[pl.ds(r, 1)],
                                          sem)
            store.start()
            store.wait()

        return carry

    jax.lax.fori_loop(0, delta_ref.shape[0], body, 0)


def scatter_add_pallas(dense: jnp.ndarray, rows: jnp.ndarray,
                       delta: jnp.ndarray, interpret: bool = False,
                       ) -> jnp.ndarray:
    """Returns dense with delta[i] added into row rows[i], np.add.at order.

    dense: (R, C); rows: (N,) int in [0, R] (R = skipped); delta: (N, C).
    """
    R, C = dense.shape
    N = rows.shape[0]
    if N == 0 or R == 0:
        return dense
    dtype = dense.dtype
    if N > SMEM_ROWS:
        for s in range(0, N, SMEM_ROWS):
            dense = scatter_add_pallas(dense, rows[s:s + SMEM_ROWS],
                                       delta[s:s + SMEM_ROWS], interpret)
        return dense
    if dtype == np.float64 and not interpret:
        raise DeviceDtypeError(
            "ps_apply: float64 blocks cannot be compiled for the TPU (Mosaic "
            "has no f64); keep the table float32 or run the numpy apply")
    cpad = (-C) % LANES
    dense_p = jnp.pad(dense, ((0, 0), (0, cpad))) if cpad else dense
    delta_p = jnp.pad(delta.astype(dtype), ((0, 0), (0, cpad)))
    blk = min(ROW_BLK, N + (-N) % SUBLANES)
    npad = (-N) % blk
    rows_i = jnp.concatenate([rows.astype(jnp.int32),
                              jnp.full((npad,), R, jnp.int32)])
    delta_p = jnp.pad(delta_p, ((0, npad), (0, 0)))
    Cp, Np = C + cpad, N + npad

    out = pl.pallas_call(
        _kernel,
        grid=(Np // blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((blk, Cp), lambda j: (j, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((R, Cp), dtype),
        scratch_shapes=[pltpu.VMEM((1, Cp), dtype),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rows_i, delta_p, dense_p)
    return out[:, :C] if cpad else out
