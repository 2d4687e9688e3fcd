"""Tests for the beyond-paper performance variants (EXPERIMENTS.md §Perf):
sequence-parallel SSD, int8-compressed gathers, bf16 state storage."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCHS, ConsistencySpec, TrainConfig, get_config,
                           reduced_config)
from repro.launch.train import run as train_run


def test_variant_configs_registered():
    assert "mamba2-130m-sp" in ARCHS
    assert "pixtral-12b-cg" in ARCHS
    assert get_config("mamba2-130m-sp").tp_strategy == "seq_ssm"
    assert get_config("pixtral-12b-cg").compress_gathers


def test_bf16_state_trains_close_to_f32():
    cfg = dataclasses.replace(reduced_config("olmo-1b"), dtype="float32")
    finals = {}
    for sd in ("float32", "bfloat16"):
        tcfg = TrainConfig(arch="x", steps=20, lr=2e-3, optimizer="adam",
                           log_every=19, state_dtype=sd,
                           consistency=ConsistencySpec(model="cvap",
                                                       staleness=3,
                                                       value_bound=0.05))
        _, hist = train_run(tcfg, cfg, mesh=None, batch_size=4, seq_len=48,
                            log=lambda *_: None)
        finals[sd] = hist[-1]["loss"]
    assert abs(finals["bfloat16"] - finals["float32"]) < 0.05, finals


def test_compressed_gather_single_device_noop():
    """At tp=1 the compress flag must be a perfect no-op."""
    from repro.models import model as M
    from repro.models.common import ShardCtx, instantiate_tree
    cfg = dataclasses.replace(reduced_config("qwen3-8b"), dtype="float32")
    cfg_c = dataclasses.replace(cfg, compress_gathers=True)
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    x1, _, _ = M.forward(cfg, ShardCtx(), params, ids, remat=False)
    x2, _, _ = M.forward(cfg_c, ShardCtx(), params, ids, remat=False)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))


@pytest.mark.slow
def test_seqpar_ssd_matches_replicated(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config
from repro.launch import mesh as mesh_lib, specs as S
from repro.models.common import instantiate_tree, pspec_tree, ShardCtx
from repro.models import model as M
from jax.sharding import PartitionSpec as P

mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(reduced_config("mamba2-130m"), dtype="float32",
                          tp_strategy="seq_ssm")
defs = M.model_defs(cfg, 4)
params = jax.device_put(instantiate_tree(defs, jax.random.key(0)),
                        S.shardings(pspec_tree(defs), mesh))
ctx = ShardCtx(model_axis="model", dp_axes=("data",), tp=4)
ids = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, (4, 64)), jnp.int32)
def fwd(p, i):
    x, _, _ = M.forward(cfg, ctx, p, i, remat=False)
    return ctx.gather_seq(x)
f = jax.jit(jax.shard_map(fwd, mesh=mesh,
            in_specs=(pspec_tree(defs), P("data", None)),
            out_specs=P("data", None, None), check_vma=False))
xd = f(params, ids)
cfg1 = dataclasses.replace(cfg, tp_strategy="replicated")
params1 = instantiate_tree(M.model_defs(cfg1, 1), jax.random.key(0))
xl, _, _ = M.forward(cfg1, ShardCtx(), params1, ids, remat=False)
err = float(jnp.max(jnp.abs(xd - xl)))
assert err < 5e-4, err
print("SEQPAR_OK", err)
""")
    assert "SEQPAR_OK" in out


@pytest.mark.slow
def test_compressed_gathers_bounded_error(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config
from repro.launch import mesh as mesh_lib, specs as S
from repro.models.common import instantiate_tree, pspec_tree, ShardCtx
from repro.models import model as M
from jax.sharding import PartitionSpec as P

mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(reduced_config("qwen3-8b"), dtype="float32",
                          compress_gathers=True)
defs = M.model_defs(cfg, 4)
params = jax.device_put(instantiate_tree(defs, jax.random.key(0)),
                        S.shardings(pspec_tree(defs), mesh))
ctx = ShardCtx(model_axis="model", dp_axes=("data",), tp=4)
ids = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, (4, 32)), jnp.int32)
def fwd(p, i):
    x, _, _ = M.forward(cfg, ctx, p, i, remat=False)
    return ctx.gather_seq(x)
f = jax.jit(jax.shard_map(fwd, mesh=mesh,
            in_specs=(pspec_tree(defs), P("data", None)),
            out_specs=P("data", None, None), check_vma=False))
xd = f(params, ids)
params1 = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
cfg1 = dataclasses.replace(cfg, compress_gathers=False)
xl, _, _ = M.forward(cfg1, ShardCtx(), params1, ids, remat=False)
rel = float(jnp.max(jnp.abs(xd - xl))) / (float(jnp.max(jnp.abs(xl))) + 1e-9)
assert rel < 0.05, rel   # lossy by design, bounded
print("CG_OK", rel)
""")
    assert "CG_OK" in out
