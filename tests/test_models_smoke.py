"""Per-architecture smoke tests (required by the brief): a REDUCED variant of
each assigned family runs one forward and one train step on CPU; output
shapes and finiteness asserted."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ConsistencySpec, TrainConfig, reduced_config
from repro.launch import steps
from repro.launch.state import init_train_state
from repro.models import model as M
from repro.models.common import ShardCtx, instantiate_tree

ARCH_IDS = sorted(ARCHS)


def _cfg(name):
    cfg = reduced_config(name)
    return dataclasses.replace(cfg, dtype="float32")


def _batch(cfg, b=2, s=32, seed=0):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s + 1)), jnp.int32)
    batch = {"ids": ids[:, :-1], "labels": ids[:, 1:]}
    if cfg.frontend is not None:
        batch["extra_emb"] = jnp.asarray(
            rng.normal(0, 0.02, (b, cfg.frontend.n_embeds, cfg.d_model)),
            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = _cfg(arch)
    ctx = ShardCtx()
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    batch = _batch(cfg)
    x, _, aux = M.forward(cfg, ctx, params, batch["ids"],
                          extra_emb=batch.get("extra_emb"), remat=False)
    assert x.shape == (2, 32, cfg.d_model)
    assert bool(jnp.isfinite(x).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_loss_finite_and_decreases(arch):
    cfg = _cfg(arch)
    tcfg = TrainConfig(arch=arch, optimizer="adam", lr=1e-3, warmup_steps=0,
                       consistency=ConsistencySpec(model="bsp"))
    state = init_train_state(cfg, tcfg, tp=1, dp=1, key=jax.random.key(0))
    step = steps.make_train_step(cfg, tcfg, mesh=None, donate=False)
    batch = _batch(cfg)
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses        # overfits one batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_unroll_matches_scan(arch):
    cfg = _cfg(arch)
    ctx = ShardCtx()
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(1))
    batch = _batch(cfg, seed=3)
    x_scan, _, _ = M.forward(cfg, ctx, params, batch["ids"],
                             extra_emb=batch.get("extra_emb"), remat=False)
    x_unroll, _, _ = M.forward(cfg, ctx, params, batch["ids"],
                               extra_emb=batch.get("extra_emb"), remat=False,
                               unroll=True)
    np.testing.assert_allclose(np.asarray(x_scan), np.asarray(x_unroll),
                               atol=2e-5)


# every arch under full recompute (ids as before) and under the saving
# policy; one dense model whose attention takes the interpreted flash kernel,
# so that the kernel's named residuals are saved too
REMAT_CASES = ([pytest.param(a, "full", id=a) for a in ARCH_IDS]
               + [pytest.param(a, "save", id=f"{a}-save") for a in ARCH_IDS]
               + [pytest.param("olmo-1b", "save-flash", id="olmo-1b-save-flash")])


@pytest.mark.parametrize("arch,plan", REMAT_CASES)
def test_remat_matches_no_remat_gradients(arch, plan, monkeypatch):
    """Gradients through the layer checkpoint, with full recompute or with
    the matmul outputs saved (``M.SAVE_MATMULS``), equal those without it."""
    cfg = _cfg(arch)
    s = 32
    if plan == "save-flash":
        monkeypatch.setenv("REPRO_PALLAS", "interpret")
        cfg = dataclasses.replace(cfg, n_heads=2, n_kv_heads=2, d_head=128,
                                  d_model=256)
        s = 128
    policy = M.SAVE_MATMULS if plan.startswith("save") else None
    ctx = ShardCtx()
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(2))
    batch = _batch(cfg, s=s, seed=4)

    def loss(p, remat):
        l, _ = M.lm_loss(cfg, ctx, p, batch["ids"], batch["labels"],
                         extra_emb=batch.get("extra_emb"), remat=remat,
                         remat_policy=policy)
        return l

    g1 = jax.grad(lambda p: loss(p, True))(params)
    g2 = jax.grad(lambda p: loss(p, False))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)
