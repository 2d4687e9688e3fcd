"""The train step's layer-checkpoint plan (``launch/steps.py`` ``remat_plan``):
the layer stack keeps its matmul outputs and the flash kernel's residuals
(``models/model.py`` ``SAVE_MATMULS``) where they fit the device, and falls
back to full recompute where they do not, or where the device reports no
memory limit (the CPU).  Checks the budget rule, the bytes it reckons
against what the checkpoint really keeps, the ``remat_saved_bytes`` the
step reports, and that under the saving plan no matmul and no forward
flash kernel of the blocks runs twice."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ConsistencySpec, TrainConfig, reduced_config
from repro.configs.registry import get_config
from repro.launch import steps
from repro.launch.state import init_train_state
from repro.models import model as M
from repro.models.common import ShardCtx, instantiate_tree

ARCH_IDS = sorted(ARCHS)
B, S = 2, 32


def _cfg(arch="olmo-1b", dtype="bfloat16"):
    return dataclasses.replace(reduced_config(arch), dtype=dtype)


def _ids(cfg, b=B, s=S, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s + 1)), jnp.int32)


# ---------------------------------------------------------------------------
# the budget rule
# ---------------------------------------------------------------------------

STATE, N_PARAMS = 10_000_000, 1_000_000


def _threshold(saved):
    """The smallest limit at which ``saved`` bytes fit: twice them, beyond
    the state and one f32 copy of the parameters."""
    return STATE + 4 * N_PARAMS + 2 * saved


def test_plan_saves_when_the_named_bytes_fit():
    cfg = _cfg()
    saved = M.saved_bytes(cfg, ShardCtx(), B, S)
    assert saved > 0
    policy, got = steps.remat_plan(cfg, ShardCtx(), (B, S), N_PARAMS, STATE,
                                   _threshold(saved))
    assert policy is M.SAVE_MATMULS and got == saved


def test_plan_falls_back_just_under_the_threshold():
    cfg = _cfg()
    saved = M.saved_bytes(cfg, ShardCtx(), B, S)
    assert steps.remat_plan(cfg, ShardCtx(), (B, S), N_PARAMS, STATE,
                            _threshold(saved) - 1) == (None, 0)


def test_plan_falls_back_where_the_device_reports_no_limit():
    assert steps.remat_plan(_cfg(), ShardCtx(), (B, S), N_PARAMS, STATE,
                            None) == (None, 0)
    assert steps.device_bytes_limit(None) is None      # the CPU reports none


def test_plan_falls_back_where_nothing_is_named():
    """A stack of recurrent mixers with no FFN names nothing: full recompute."""
    cfg = _cfg("mamba2-130m")
    assert M.saved_bytes(cfg, ShardCtx(), B, S) == 0
    assert steps.remat_plan(cfg, ShardCtx(), (B, S), N_PARAMS, STATE,
                            2**50) == (None, 0)


def test_saved_bytes_of_the_olmo_cell(monkeypatch):
    """``olmo-1b-4l.cvap3``'s widths (d 2048, 16 x 128 heads, d_ff 8192, 4
    layers, 8 x 2048 tokens, bf16) with the flash kernel: per layer q, k, v,
    the kernel's output and f32 logsumexp, the residual after attention,
    and the w_in and w_gate outputs."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=4,
                              dtype="bfloat16")
    b, s, d, h, dh, ff = 8, 2048, 2048, 16, 128, 8192
    layer = (3 * b * s * d * 2 + b * h * s * (dh * 2 + 4) + b * s * d * 2
             + 2 * b * s * ff * 2)
    assert M.saved_bytes(cfg, ShardCtx(), b, s) == 4 * layer == 3_493_855_232
    monkeypatch.setenv("REPRO_PALLAS", "off")
    assert M.saved_bytes(cfg, ShardCtx(), b, s) == 4 * (
        layer - b * h * s * (dh * 2 + 4))


def _kept_bytes(cfg, params, ids, policy):
    """Bytes of the residuals the linearized loss holds for its backward."""
    _, f_jvp = jax.linearize(lambda p: M.lm_loss(
        cfg, ShardCtx(), p, ids[:, :-1], ids[:, 1:], remat=True,
        remat_policy=policy)[0], params)
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(f_jvp))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_saved_bytes_are_what_the_checkpoint_keeps(arch):
    """The reckoned bytes are what the saving policy keeps beyond full
    recompute, for every architecture (bf16, as deployed)."""
    cfg = _cfg(arch)
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    ids = _ids(cfg)
    extra = (_kept_bytes(cfg, params, ids, M.SAVE_MATMULS)
             - _kept_bytes(cfg, params, ids, None))
    assert extra == M.saved_bytes(cfg, ShardCtx(), B, S)


def test_saved_bytes_under_tensor_parallelism():
    """The same on a 2-way model axis, in each layout: head-TP (with kv
    heads duplicated, and MLA), seq-TP, replicated and seq_ssm, from the
    local shapes inside ``shard_map``."""
    from conftest import run_devices_subprocess
    out = run_devices_subprocess("""
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import reduced_config
from repro.launch import mesh as mesh_lib
from repro.models import model as M
from repro.models.common import ShardCtx, instantiate_tree, pspec_tree
mesh = mesh_lib.make_mesh((1, 2), ("data", "model"))
ctx = ShardCtx(model_axis="model", dp_axes=("data",), tp=2)
for arch, strategy in [("olmo-1b", None), ("gemma2-2b", None),
                       ("recurrentgemma-9b", None),
                       ("deepseek-v2-lite-16b", None), ("olmo-1b", "replicated"),
                       ("mamba2-130m", "seq_ssm")]:
    cfg = dataclasses.replace(reduced_config(arch), dtype="bfloat16",
                              tp_strategy=strategy or reduced_config(arch).tp_strategy)
    defs = M.model_defs(cfg, 2)
    params = jax.eval_shape(lambda: instantiate_tree(defs, jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    kept = {}
    for name, policy in (("full", None), ("save", M.SAVE_MATMULS)):
        def body(p, i):
            _, f = jax.linearize(lambda q: M.lm_loss(
                cfg, ctx, q, i[:, :-1], i[:, 1:], remat=True,
                remat_policy=policy)[0], p)
            kept[name] = sum(x.size * x.dtype.itemsize
                             for x in jax.tree.leaves(f))
            return jnp.zeros(())
        jax.eval_shape(jax.shard_map(
            body, mesh=mesh, in_specs=(pspec_tree(defs), P("data", None)),
            out_specs=P(), check_vma=False), params, ids)
    assert kept["save"] - kept["full"] == M.saved_bytes(cfg, ctx, 2, 32), (
        arch, strategy, kept, M.saved_bytes(cfg, ctx, 2, 32))
    print("SAVED_OK", arch, strategy)
""", n_devices=2)
    assert out.count("SAVED_OK") == 6, out


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def test_step_reports_the_bytes_its_plan_keeps(monkeypatch):
    """``remat_saved_bytes`` is 0 on the CPU (no limit) and without remat,
    and the reckoned bytes where the device has room; the loss is the same
    under either plan (f32: in bf16 the saved values are rounded to bf16
    where a fused recompute may keep more bits)."""
    cfg = _cfg(dtype="float32")
    ids = _ids(cfg)
    batch = {"ids": ids[:, :-1], "labels": ids[:, 1:]}

    def first_step(**kw):
        tcfg = TrainConfig(arch=cfg.name, optimizer="adam", lr=1e-3,
                           warmup_steps=0,
                           consistency=ConsistencySpec(model="bsp"), **kw)
        state = init_train_state(cfg, tcfg, tp=1, dp=1, key=jax.random.key(0))
        _, m = steps.make_train_step(cfg, tcfg, mesh=None,
                                     donate=False)(state, batch)
        return float(m["loss"]), float(m["remat_saved_bytes"])

    loss_full, saved_full = first_step()
    _, saved_none = first_step(remat=False)
    monkeypatch.setattr(steps, "device_bytes_limit", lambda mesh: 2**40)
    loss_save, saved = first_step()
    assert saved_full == saved_none == 0
    assert saved == M.saved_bytes(cfg, ShardCtx(), B, S) > 0
    np.testing.assert_allclose(loss_save, loss_full, rtol=1e-6)


def _block_work(policy, remat, monkeypatch):
    """(dot_generals under the ``blocks`` scope, forward flash kernels) in
    the jaxpr of the loss gradient of a 2-layer model whose attention takes
    the interpreted flash kernel (dh 128, s 256)."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    cfg = dataclasses.replace(reduced_config("olmo-1b"), n_heads=2,
                              n_kv_heads=2, d_head=128, d_model=256,
                              dtype="float32")
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    ids = _ids(cfg, b=1, s=256)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: M.lm_loss(
        cfg, ShardCtx(), p, ids[:, :-1], ids[:, 1:], remat=remat,
        remat_policy=policy)[0]))(params)
    dots = fwd = 0

    def walk(j, scopes):
        nonlocal dots, fwd
        for e in j.eqns:
            here = f"{scopes}/{e.source_info.name_stack}"
            if e.primitive.name == "pallas_call":
                fwd += "splash_mha_fwd" in here
                continue                     # a kernel body is no matmul of the model
            dots += e.primitive.name == "dot_general" and "blocks" in here
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub, here)

    walk(jaxpr.jaxpr, "")
    return dots, fwd


def test_saving_plan_runs_no_block_matmul_twice(monkeypatch):
    """Under SAVE_MATMULS the gradient holds as many block matmuls and
    forward flash kernels as without a checkpoint, and fewer than under
    full recompute, which runs q, k, v, wo, w_in, w_gate and the kernel
    again (w_out's output is not needed, so no plan runs it twice)."""
    none = _block_work(None, False, monkeypatch)
    full = _block_work(None, True, monkeypatch)
    save = _block_work(M.SAVE_MATMULS, True, monkeypatch)
    assert save == none
    assert full == (none[0] + 6, 2) and none[1] == 1
