"""Compile the main-path kernels for a described TPU v5e at real widths.

Nothing runs: the TPU compiler that ships with jaxlib compiles each kernel
for a chip that is described, not attached, and refuses what the chip would
refuse (unaligned blocks, VMEM overuse, f64).  The topology is described in
a module fixture, never at import time, so every xdist worker collects the
same tests and only the worker running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_ps_apply_compiles_at_shard_size(one_chip, no_persistent_cache):
    """Half the NYTimes vocabulary (102,660 rows over 2 shards) x 128
    topics, f32, with a 4,096-row batch: the block stays in HBM."""
    from repro.kernels.ps_apply import kernel
    fn = jax.jit(kernel.scatter_add_pallas, static_argnames=("interpret",),
                 donate_argnums=(0,))
    compiled = fn.lower(_sds((51_330, 128), jnp.float32, one_chip),
                        _sds((4096,), jnp.int32, one_chip),
                        _sds((4096, 128), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_ps_apply_refuses_f64_by_name(one_chip, no_persistent_cache):
    from repro.kernels.ps_apply import kernel
    with jax.enable_x64(True):
        with pytest.raises(kernel.DeviceDtypeError):
            jax.jit(kernel.scatter_add_pallas).lower(
                _sds((64, 128), jnp.float64, one_chip),
                _sds((8,), jnp.int32, one_chip),
                _sds((8, 128), jnp.float64, one_chip))


def test_topk_mag_compiles(one_chip, no_persistent_cache):
    from repro.kernels.topk_mag import kernel
    compiled = jax.jit(kernel.topk_mag_pallas).lower(
        _sds((4096,), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_rglru_scan_compiles_at_recurrentgemma_width(one_chip,
                                                     no_persistent_cache):
    """recurrentgemma-9b's recurrence width 4096 over a 4096-token
    sequence, bf16 activations."""
    from repro.kernels.rglru_scan import kernel
    x = _sds((1, 4096, 4096), jnp.bfloat16, one_chip)
    lam = _sds((4096,), jnp.float32, one_chip)
    compiled = jax.jit(kernel.rglru_pallas).lower(x, x, x, lam).compile()
    assert _has_kernel(compiled)
