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


def test_flash_attention_compiles_at_the_train_cell_shape(
        one_chip, no_persistent_cache, monkeypatch):
    """``olmo-1b-4l.cvap3``'s attention, (8, 2048, 16, 128) bf16 causal,
    forward and backward through the model's ``attention_core``: the
    forward, dq and dk/dv kernels are in the program, and each carries the
    ``attention_core`` scope in its ``op_name``, so that its device time
    counts in ``step_ms.attention_core``."""
    import re

    import numpy as np
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import scopes
    monkeypatch.setenv("REPRO_PALLAS", "on")
    from repro.models.attention import attention_core
    pos = np.arange(2048, dtype=np.int32)

    def loss(q, k, v):
        return jnp.sum(attention_core(q, k, v, pos, pos).astype(jnp.float32))

    kv = _sds((8, 2048, 16, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        _sds((8, 2048, 16, 1, 128), jnp.bfloat16, one_chip), kv, kv).compile()
    # a kernel's instruction spans lines (its kernel metadata holds one)
    text = compiled.as_text()
    names = [re.compile(r'op_name="([^"]*)"').search(text, m.end()).group(1)
             for m in re.finditer(r'custom_call_target="tpu_custom_call"',
                                  text)]
    assert len(names) == 3, names
    for part in ("fwd", "dq", "dkv"):
        assert any(part in n.rsplit("/", 2)[-2] for n in names), (part, names)
    assert all(scopes.scope_of(n) == "attention_core" for n in names), names


def test_olmo_train_step_saves_its_matmul_outputs(one_chip, no_persistent_cache,
                                                  monkeypatch):
    """``olmo-1b-4l.cvap3``'s whole train step (the cell's configuration,
    8 x 2048 tokens, CVAP(3, 0.05), Adam) with the layer checkpoint's saving
    plan, as a device that reports 16 GiB chooses it: the forward flash
    kernel is one instruction of the scanned forward, so it runs once a
    layer and the backward runs it no more, and the program fits the v5e's
    16 GiB."""
    import json
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from bench.train_cell import model_config
    from repro.configs import ConsistencySpec, TrainConfig
    from repro.launch import steps
    from repro.launch.state import init_train_state
    monkeypatch.setenv("REPRO_PALLAS", "on")
    monkeypatch.setattr(steps, "device_bytes_limit", lambda mesh: 16 * 2**30)
    with open(os.path.join(root, "bench", "configs", "olmo-1b-4l.json")) as f:
        cfg = model_config(json.load(f), "olmo-1b")
    tcfg = TrainConfig(arch=cfg.name, lr=3e-4, warmup_steps=0,
                       optimizer="adam", consistency=ConsistencySpec(
                           model="cvap", staleness=3, value_bound=0.05))
    state = jax.eval_shape(lambda k: init_train_state(cfg, tcfg, 1, 1, k),
                           jax.random.key(0))
    state = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), state)
    batch = {k: _sds((8, 2048), jnp.int32, one_chip) for k in ("ids", "labels")}
    compiled = steps.make_train_step(cfg, tcfg, None).lower(
        state, batch).compile()
    text = compiled.as_text()
    names = [re.compile(r'op_name="([^"]*)"').search(text, m.end()).group(1)
             for m in re.finditer(r'custom_call_target="tpu_custom_call"',
                                  text)]
    fwd = [n for n in names if "splash_mha_fwd" in n]
    assert len(fwd) == 1 and "transpose(" not in fwd[0], names
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert peak < 16 * 2**30, peak
