"""Multi-device tests (8 fake host devices, out-of-process so the main test
session keeps 1 device as the brief requires)."""
import pytest

# every test here compiles SPMD programs in an 8-device subprocess — minutes,
# not seconds.  Quick loop: -m "not slow"; tier-1 stays the full suite.
pytestmark = pytest.mark.slow


def test_forward_parity_dist_vs_local(devices8):
    """Distributed (tp=2, dp=4) forward == single-device, all strategies."""
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config
from repro.launch import mesh as mesh_lib, specs as S
from repro.models.common import instantiate_tree, pspec_tree, ShardCtx
from repro.models import model as M
from jax.sharding import PartitionSpec as P

mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
for arch in ["qwen3-8b", "gemma2-2b", "mamba2-130m", "recurrentgemma-9b",
             "deepseek-v2-lite-16b", "olmoe-1b-7b"]:
    cfg = dataclasses.replace(reduced_config(arch), dtype="float32")
    if cfg.moe:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    defs = M.model_defs(cfg, 2)
    params = jax.device_put(instantiate_tree(defs, jax.random.key(0)),
                            S.shardings(pspec_tree(defs), mesh))
    ctx = ShardCtx(model_axis="model", dp_axes=("data",), tp=2)
    ids = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, (8, 16)), jnp.int32)
    def fwd(p, ids):
        x, _, _ = M.forward(cfg, ctx, p, ids, remat=False)
        return ctx.gather_seq(x) if cfg.tp_strategy in ("head", "seq") else x
    f = jax.jit(jax.shard_map(fwd, mesh=mesh,
                in_specs=(pspec_tree(defs), P("data", None)),
                out_specs=P("data", None, None), check_vma=False))
    xd = f(params, ids)
    params1 = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    xl, _, _ = M.forward(cfg, ShardCtx(), params1, ids, remat=False)
    err = float(jnp.max(jnp.abs(xd - xl)))
    assert err < 2e-4, (arch, err)
    print(arch, "OK", err)
""")
    assert out.count("OK") == 6


def test_train_step_first_loss_parity(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config, ConsistencySpec, TrainConfig
from repro.launch import mesh as mesh_lib, steps, specs as S
from repro.launch.state import init_train_state, init_local_state, add_dp_axis

mesh = mesh_lib.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = dataclasses.replace(reduced_config("olmo-1b"), dtype="float32")
tcfg = TrainConfig(arch="olmo-1b", optimizer="adam", lr=1e-3, warmup_steps=0,
                   consistency=ConsistencySpec(model="bsp"))
rng = np.random.default_rng(0)
ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 17)), jnp.int32)
batch = {"ids": ids[:, :-1], "labels": ids[:, 1:]}
state = init_train_state(cfg, tcfg, tp=2, dp=4, key=jax.random.key(0))
state_spec = S.resolve_tree(S.train_state_pspecs(cfg, tcfg, 2), ("pod", "data"))
state = jax.device_put(state, S.shardings(state_spec, mesh))
fn = steps.make_train_step(cfg, tcfg, mesh, donate=False)
_, md = fn(state, batch)

st1 = add_dp_axis(init_local_state(cfg, tcfg, tp=1, key=jax.random.key(0)), 1)
fn1 = steps.make_train_step(cfg, tcfg, None, donate=False)
_, ml = fn1(st1, batch)
err = abs(float(md["loss"]) - float(ml["loss"]))
assert err < 2e-4, (float(md["loss"]), float(ml["loss"]))
print("OK", err)
""")
    assert "OK" in out


def test_bsp_replicas_stay_identical_vap_bounded(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config, ConsistencySpec, TrainConfig
from repro.launch import mesh as mesh_lib, steps, specs as S
from repro.launch.state import init_train_state
from repro.core import policies
from repro.core.sync import vap_invariant_ok

mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
cfg = dataclasses.replace(reduced_config("olmo-1b"), dtype="float32")
rng = np.random.default_rng(0)
def batch():
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 17)), jnp.int32)
    return {"ids": ids[:, :-1], "labels": ids[:, 1:]}

for model, s, v in [("bsp", 0, 0.0), ("cvap", 3, 0.02)]:
    tcfg = TrainConfig(arch="olmo-1b", optimizer="adam", lr=1e-3, warmup_steps=0,
                       consistency=ConsistencySpec(model=model, staleness=s, value_bound=v))
    state = init_train_state(cfg, tcfg, tp=2, dp=4, key=jax.random.key(0))
    spec = S.resolve_tree(S.train_state_pspecs(cfg, tcfg, 2), ("data",))
    state = jax.device_put(state, S.shardings(spec, mesh))
    fn = steps.make_train_step(cfg, tcfg, mesh, donate=False)
    for i in range(5):
        state, m = fn(state, batch())
    # replica divergence: max over leaves of per-dp spread
    div = max(float(jnp.max(jnp.abs(x - x[0:1]))) for x in jax.tree.leaves(state.params))
    if model == "bsp":
        assert div < 1e-5, div
        print("BSP identical OK", div)
    else:
        pol = policies.from_spec(tcfg.consistency)
        sync0 = jax.tree.map(lambda x: x[0], state.sync)
        assert bool(vap_invariant_ok(pol, sync0)), "VAP invariant violated"
        print("CVAP bounded OK", div)
""")
    assert "BSP identical OK" in out and "CVAP bounded OK" in out


def test_serve_parity(devices8):
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config, InputShape
from repro.launch import mesh as mesh_lib, steps, specs as S
from repro.models.common import instantiate_tree, pspec_tree, ShardCtx
from repro.models import model as M

mesh = mesh_lib.make_mesh((2, 2, 2), ("pod", "data", "model"))
for arch in ["gemma2-2b", "musicgen-medium", "mamba2-130m"]:
    cfg = dataclasses.replace(reduced_config(arch), dtype="float32")
    defs = M.model_defs(cfg, 2)
    params = jax.device_put(instantiate_tree(defs, jax.random.key(0)),
                            S.shardings(pspec_tree(defs), mesh))
    shape = InputShape("p", seq_len=16, global_batch=8, mode="prefill")
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (8, 16)), jnp.int32)
    batch = {"ids": ids}
    if cfg.frontend:
        batch["extra_emb"] = jnp.asarray(rng.normal(0,.01,(8, cfg.frontend.n_embeds, cfg.d_model)), jnp.float32)
    nxt, caches = steps.make_prefill_step(cfg, mesh, shape)(params, batch)
    nxt2, _ = steps.make_serve_step(cfg, mesh, shape)(params, caches,
        {"ids": nxt[:, None], "pos": jnp.full((8,), 16, jnp.int32)})
    params1 = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    ctx1 = ShardCtx()
    l1, c1 = M.prefill(cfg, ctx1, params1, ids, capacity=16, extra_emb=batch.get("extra_emb"))
    n1 = jnp.argmax(l1, -1).astype(jnp.int32)
    l2, _ = M.decode_step(cfg, ctx1, params1, n1[:, None], jnp.full((8,), 16, jnp.int32), c1)
    n2 = jnp.argmax(l2, -1)
    assert bool(jnp.all(nxt == n1)) and bool(jnp.all(nxt2 == n2)), arch
    print(arch, "OK")
""")
    assert out.count("OK") == 3


def test_hierarchical_and_compressed_sync(devices8):
    """Beyond-paper options lower and run on a pod×data×model mesh."""
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config, ConsistencySpec, TrainConfig
from repro.launch import mesh as mesh_lib, steps, specs as S
from repro.launch.state import init_train_state
mesh = mesh_lib.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = dataclasses.replace(reduced_config("olmo-1b"), dtype="float32")
rng = np.random.default_rng(0)
ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 17)), jnp.int32)
batch = {"ids": ids[:, :-1], "labels": ids[:, 1:]}
tcfg = TrainConfig(arch="olmo-1b", optimizer="adam", lr=1e-3, warmup_steps=0,
                   consistency=ConsistencySpec(model="cap", staleness=1),
                   quantize_sync=True, hierarchical_sync=2)
state = init_train_state(cfg, tcfg, tp=2, dp=4, key=jax.random.key(0))
spec = S.resolve_tree(S.train_state_pspecs(cfg, tcfg, 2), ("pod", "data"))
state = jax.device_put(state, S.shardings(spec, mesh))
fn = steps.make_train_step(cfg, tcfg, mesh, donate=False)
losses = []
for i in range(6):
    state, m = fn(state, batch)
    losses.append(float(m["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
print("OK", losses[0], losses[-1])
""")
    assert "OK" in out


def test_gradient_scale_calibration(devices8):
    """The universal grad rule — (psum if replicated else id)/tp — must make
    distributed per-leaf gradients match single-device gradients at ratio 1.0
    for every TP strategy (this caught a tp× seed-multiplicity bug)."""
    out = devices8(_GRAD_CAL_CODE)
    assert out.count("RATIO_OK") == 4, out


_GRAD_CAL_CODE = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import reduced_config
from repro.launch import mesh as mesh_lib, specs as S
from repro.models.common import instantiate_tree, pspec_tree, ShardCtx, ParamDef
from repro.models import model as M
from jax.sharding import PartitionSpec as P
import jax.tree_util as jtu

mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
tp = 4
for arch, strategy in [("olmo-1b", None), ("gemma2-2b", None),
                       ("mamba2-130m", None), ("mamba2-130m", "seq_ssm")]:
    cfg = dataclasses.replace(reduced_config(arch), dtype="float32")
    if strategy: cfg = dataclasses.replace(cfg, tp_strategy=strategy)
    defs = M.model_defs(cfg, tp)
    params = jax.device_put(instantiate_tree(defs, jax.random.key(0)),
                            S.shardings(pspec_tree(defs), mesh))
    ctx = ShardCtx(model_axis="model", dp_axes=("data",), tp=tp)
    rep_mask = jax.tree.map(lambda d: "model" not in (d.shard or ()), defs,
                            is_leaf=lambda x: isinstance(x, ParamDef))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    def loss_d(p, i, l):
        return M.lm_loss(cfg, ctx, p, i, l, remat=False)[0]
    def grad_fn(p, i, l):
        g = jax.grad(loss_d)(p, i, l)
        g = jax.tree.map(lambda x, rep: (jax.lax.psum(x, "model") if rep else x) / tp,
                         g, rep_mask)
        return jax.tree.map(lambda x: jax.lax.pmean(x, "data"), g)
    g = jax.jit(jax.shard_map(grad_fn, mesh=mesh,
                in_specs=(pspec_tree(defs), P("data", None), P("data", None)),
                out_specs=pspec_tree(defs), check_vma=False))(params, ids, labels)
    params1 = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    def loss_l(p):
        return M.lm_loss(cfg, ShardCtx(), p, ids, labels, remat=False)[0]
    gl = jax.grad(loss_l)(params1)
    flat_l = {jtu.keystr(p): np.asarray(x) for p, x in jtu.tree_flatten_with_path(gl)[0]}
    for path, leaf in jtu.tree_flatten_with_path(g)[0]:
        k = jtu.keystr(path)
        a = np.asarray(jax.device_get(leaf)); b = flat_l.get(k)
        if b is None or a.shape != b.shape or np.abs(b).max() < 1e-7: continue
        r = float((a * b).sum() / (b * b).sum())
        assert abs(r - 1) < 5e-3, (arch, strategy, k, r)
    print("RATIO_OK", arch, strategy)
"""
