"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
ref.py oracle, swept over shapes and dtypes (brief requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import kernel as fk
from repro.kernels.flash_attention import ref as fr
from repro.kernels.rglru_scan import kernel as rk
from repro.kernels.rglru_scan import ref as rr
from repro.kernels.ssd_scan import kernel as sk
from repro.kernels.ssd_scan import ref as sr
from repro.kernels.vap_accum import kernel as vk
from repro.kernels.vap_accum import ref as vr

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=5e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # b, s, kvh, G, dh, window, cap
    (2, 512, 2, 1, 128, None, None),       # causal MHA
    (1, 1024, 2, 1, 128, None, None),      # two 512-blocks: one skipped
    (1, 512, 2, 2, 128, None, None),       # GQA: multi-query form per kv head
    (1, 512, 1, 2, 128, 200, None),        # sliding window
    (1, 512, 2, 1, 128, None, 30.0),       # logit soft-cap
    (1, 512, 1, 2, 256, 256, 50.0),        # gemma2-like: dh 256, window, cap
]


def _flash_inputs(case, dtype, rng):
    b, s, kvh, G, dh, _, _ = case
    q = jnp.asarray(rng.normal(0, 1, (b, s, kvh, G, dh)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (b, s, kvh, dh)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (b, s, kvh, dh)), dtype)
    return q, k, v


def _flash_rng():
    """Each flash test draws from its own generator, so that adding one
    leaves the module RNG's draws for the tests below unchanged."""
    return np.random.default_rng(14)


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dtype):
    *_, window, cap = case
    q, k, v = _flash_inputs(case, dtype, _flash_rng())
    pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    out = fk.flash_attention_pallas(q, k, v, window=window, cap=cap,
                                    interpret=True)
    ref = fr.attention(q, k, v, pos, pos, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad(case, dtype):
    """The kernel's backward (dq and dk/dv kernels) == jax.grad of ref.py,
    for a random cotangent."""
    *_, window, cap = case
    rng = _flash_rng()
    q, k, v = _flash_inputs(case, dtype, rng)
    pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    ct = jnp.asarray(rng.normal(0, 1, q.shape), jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * ct)

    got = jax.grad(loss(lambda q, k, v: fk.flash_attention_pallas(
        q, k, v, window=window, cap=cap, interpret=True)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: fr.attention(
        q, k, v, pos, pos, window=window, cap=cap)), (0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == dtype, name
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   err_msg=f"d{name}", **_tol(dtype))


def test_flash_matches_model_chunked_core():
    """kernel == ref == the model-side banded chunked core."""
    from repro.models.attention import attention_core
    q, k, v = _flash_inputs((1, 1024, 2, 1, 128, None, None), jnp.float32,
                            _flash_rng())
    pos = jnp.arange(q.shape[1], dtype=jnp.int32)
    for window in (None, 128):
        a = fk.flash_attention_pallas(q, k, v, window=window, interpret=True)
        c = attention_core(q, k, v, pos, pos, window=window, chunk=256)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=5e-5)


def test_flash_refuses_shapes_it_does_not_take():
    q = jnp.zeros((1, 300, 1, 1, 128), jnp.float32)
    k = jnp.zeros((1, 300, 1, 128), jnp.float32)
    with pytest.raises(ValueError, match="does not take"):
        fk.flash_attention_pallas(q, k, k, interpret=True)


# ---------------------------------------------------------------------------
# attention-core dispatch: which calls take the flash kernel
# ---------------------------------------------------------------------------

_S = 512
_ARANGE = np.arange(_S, dtype=np.int32)


def _route_case(name):
    """(q, k, v, q_pos, k_pos, traced positions) of one call shape."""
    bf = jnp.bfloat16

    def qkv(sq, skv, kvh, G, dh, dv):
        return (jnp.zeros((1, sq, kvh, G, dh), bf),
                jnp.zeros((1, skv, kvh, dh), bf),
                jnp.zeros((1, skv, kvh, dv), bf))

    if name == "train_mha":
        return (*qkv(_S, _S, 2, 1, 128, 128), _ARANGE, _ARANGE, False)
    if name == "train_gqa":
        return (*qkv(_S, _S, 1, 4, 128, 128), _ARANGE, _ARANGE, False)
    if name == "decode":            # one new token against a 512-slot cache
        return (*qkv(1, _S, 2, 1, 128, 128), np.full((1, 1), 7, np.int32),
                np.zeros((1, _S), np.int32), False)
    if name == "seq_tp_shard":      # q is a shard, its positions traced
        return (*qkv(_S, _S, 2, 1, 128, 128), _ARANGE, _ARANGE, True)
    if name == "offset_positions":  # a prefill tail: 512..1023 against 512 keys
        return (*qkv(_S, _S, 2, 1, 128, 128), _ARANGE + _S, _ARANGE + _S,
                False)
    if name == "mla_dims":          # DeepSeek MLA: qk 192, v 128
        return (*qkv(_S, _S, 2, 1, 192, 128), _ARANGE, _ARANGE, False)
    if name == "dh_64":
        return (*qkv(_S, _S, 2, 1, 64, 64), _ARANGE, _ARANGE, False)
    if name == "s_300":
        a = np.arange(300, dtype=np.int32)
        return (*qkv(300, 300, 2, 1, 128, 128), a, a, False)
    raise KeyError(name)


ROUTES = [("train_mha", "interpret", "kernel"),
          ("train_gqa", "interpret", "kernel"),
          ("train_mha", "off", "core"),
          ("decode", "interpret", "core"),
          ("seq_tp_shard", "interpret", "core"),
          ("offset_positions", "interpret", "core"),
          ("mla_dims", "interpret", "core"),
          ("dh_64", "interpret", "core"),
          ("s_300", "interpret", "core")]


@pytest.mark.parametrize("name,mode,path", ROUTES)
def test_attention_core_route(monkeypatch, name, mode, path):
    """Train and prefill shapes take the kernel; decode, sequence shards,
    positions not 0..s-1, MLA's dims, head dims and lengths the kernel
    does not take, and Pallas off run the jnp core."""
    from repro.models.attention import attention_core
    monkeypatch.setenv("REPRO_PALLAS", mode)
    q, k, v, qp, kp, traced = _route_case(name)
    if traced:
        jaxpr = jax.make_jaxpr(lambda q, k, v, qp: attention_core(
            q, k, v, qp, kp))(q, k, v, qp)
    else:
        jaxpr = jax.make_jaxpr(lambda q, k, v: attention_core(
            q, k, v, qp, kp))(q, k, v)
    took = "kernel" if "pallas_call" in str(jaxpr) else "core"
    assert took == path


@pytest.mark.parametrize("dtype,rtol", [("float32", 1e-4), ("bfloat16", 3e-2)])
def test_lm_loss_takes_the_kernel_and_matches_the_core(monkeypatch, dtype,
                                                       rtol):
    """A 2-layer OLMo-family model (dh 128, s 512): loss and gradients
    through the interpreted kernel == through the jnp core, each gradient
    within ``rtol`` of the core's by norm (bf16: a few roundings of 2^-8)."""
    import dataclasses

    from repro.configs import reduced_config
    from repro.models import model as M
    from repro.models.common import ShardCtx, instantiate_tree

    cfg = dataclasses.replace(reduced_config("olmo-1b"), n_heads=2,
                              n_kv_heads=2, d_head=128, d_model=256,
                              dtype=dtype)
    params = instantiate_tree(M.model_defs(cfg, 1), jax.random.key(0))
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 513)), jnp.int32)

    def run(mode):
        monkeypatch.setenv("REPRO_PALLAS", mode)
        fn = jax.value_and_grad(lambda p: M.lm_loss(
            cfg, ShardCtx(), p, ids[:, :-1], ids[:, 1:])[0])
        return str(jax.make_jaxpr(fn)(params)), jax.jit(fn)(params)

    jaxpr_k, (loss_k, g_k) = run("interpret")
    jaxpr_c, (loss_c, g_c) = run("off")
    assert "pallas_call" in jaxpr_k and "pallas_call" not in jaxpr_c
    np.testing.assert_allclose(float(loss_k), float(loss_c), rtol=rtol / 10)
    for a, b in zip(jax.tree.leaves(g_k), jax.tree.leaves(g_c)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= rtol * np.linalg.norm(b)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # b, l, h, p, g, n, chunk
    (2, 64, 4, 8, 2, 16, 16),
    (1, 100, 6, 16, 1, 32, 32),     # padding path
    (2, 256, 4, 64, 2, 128, 64),    # production-like dims
    (1, 32, 2, 8, 2, 8, 32),        # single chunk
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(case, dtype):
    b, l, h, p, g, n, chunk = case
    x = jnp.asarray(RNG.normal(0, 1, (b, l, h, p)), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.1, 1, (h,)), jnp.float32)
    B = jnp.asarray(RNG.normal(0, 1, (b, l, g, n)), dtype)
    C = jnp.asarray(RNG.normal(0, 1, (b, l, g, n)), dtype)
    init = jnp.asarray(RNG.normal(0, 0.5, (b, h, p, n)), jnp.float32)
    y1, s1 = sk.ssd_scan_pallas(x, dt, A, B, C, chunk, initial_state=init,
                                interpret=True)
    y2, s2 = sr.ssd_chunked(x, dt, A, B, C, chunk, initial_state=init)
    # bf16 inputs: kernel carries chunk states in f32 while the oracle's bulk
    # einsums stay bf16 — accumulation-order noise scales with |y| ~ O(5)
    tol = (dict(atol=1e-1, rtol=5e-2) if dtype == jnp.bfloat16
           else dict(atol=5e-5, rtol=1e-4))
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_matches_stepwise():
    """Chunked == naive per-step recurrence (the ultimate oracle)."""
    b, l, h, p, g, n = 1, 40, 4, 8, 2, 16
    x = jnp.asarray(RNG.normal(0, 1, (b, l, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.1, 1, (h,)), jnp.float32)
    B = jnp.asarray(RNG.normal(0, 1, (b, l, g, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(0, 1, (b, l, g, n)), jnp.float32)
    y, st = sr.ssd_chunked(x, dt, A, B, C, chunk=8)
    Bh, Ch = jnp.repeat(B, h // g, 2), jnp.repeat(C, h // g, 2)
    hstate = jnp.zeros((b, h, p, n))
    for t in range(l):
        yt, hstate = sr.ssd_step(hstate, x[:, t], dt[:, t], A, Bh[:, t], Ch[:, t])
        np.testing.assert_allclose(np.asarray(y[:, t]), np.asarray(yt),
                                   atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(hstate), atol=1e-5)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

RGLRU_CASES = [(2, 64, 128), (1, 100, 50), (3, 256, 256), (1, 128, 4096)]


@pytest.mark.parametrize("case", RGLRU_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_linear_recurrence(case, dtype):
    b, l, w = case
    a = jnp.asarray(RNG.uniform(0.7, 0.999, (b, l, w)), dtype)
    bb = jnp.asarray(RNG.normal(0, 0.1, (b, l, w)), dtype)
    init = jnp.asarray(RNG.normal(0, 1, (b, w)), jnp.float32)
    h1, l1 = rk.linear_recurrence_pallas(a, bb, initial=init, interpret=True)
    h2, l2 = rr.linear_recurrence(a, bb, initial=init)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_rglru_full_gate_path():
    b, l, w = 2, 96, 64
    x = jnp.asarray(RNG.normal(0, 1, (b, l, w)), jnp.float32)
    r = jnp.asarray(RNG.uniform(0, 1, (b, l, w)), jnp.float32)
    i = jnp.asarray(RNG.uniform(0, 1, (b, l, w)), jnp.float32)
    lam = jnp.asarray(RNG.normal(0, 1, (w,)), jnp.float32)
    h1, l1 = rk.rglru_pallas(x, r, i, lam, interpret=True)
    h2, l2 = rr.rglru(x, r, i, lam)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=5e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=5e-5)


def test_rglru_step_consistency():
    """Sequential steps == full scan."""
    b, l, w = 1, 20, 16
    x = jnp.asarray(RNG.normal(0, 1, (b, l, w)), jnp.float32)
    r = jnp.asarray(RNG.uniform(0, 1, (b, l, w)), jnp.float32)
    i = jnp.asarray(RNG.uniform(0, 1, (b, l, w)), jnp.float32)
    lam = jnp.asarray(RNG.normal(0, 1, (w,)), jnp.float32)
    h_full, _ = rr.rglru(x, r, i, lam)
    h = jnp.zeros((b, w))
    for t in range(l):
        _, h = rr.rglru_step(h, x[:, t], r[:, t], i[:, t], lam)
        np.testing.assert_allclose(np.asarray(h_full[:, t]), np.asarray(h),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# vap accum
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [64, 8192, 8193, 100_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vap_accum(n, dtype):
    p = jnp.asarray(RNG.normal(0, 1, n), dtype)
    d = jnp.asarray(RNG.normal(0, 0.01, n), dtype)
    u = jnp.asarray(RNG.normal(0, 0.01, n), dtype)
    p1, d1, m1 = vk.vap_accum_pallas(p, d, u, interpret=True)
    p2, d2, m2 = vr.vap_accum(p, d, u)
    np.testing.assert_allclose(np.asarray(p1, np.float32),
                               np.asarray(p2, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(d1, np.float32),
                               np.asarray(d2, np.float32), **_tol(dtype))
    assert abs(float(m1) - float(m2)) < 1e-2


def test_vap_accum_tree():
    from repro.kernels.vap_accum.ops import vap_accum_tree
    tree = {"a": jnp.ones((4, 4)), "b": {"c": jnp.zeros(7)}}
    delta = jax.tree.map(jnp.zeros_like, tree)
    upd = jax.tree.map(lambda x: x * 0 + 0.5, tree)
    p2, d2, m = vap_accum_tree(tree, delta, upd)
    assert float(m) == 0.5
    np.testing.assert_allclose(np.asarray(p2["a"]), 1.5)


# ---------------------------------------------------------------------------
# ps apply (segment scatter-add)
# ---------------------------------------------------------------------------

from repro.kernels.ps_apply import kernel as pk          # noqa: E402
from repro.kernels.ps_apply import ref as pr             # noqa: E402
from repro.kernels.topk_mag import kernel as tk          # noqa: E402
from repro.kernels.topk_mag import ref as tr             # noqa: E402

PS_APPLY_CASES = [
    # R, C, N — incl. duplicates-heavy, single row, wide block, big batch
    (13, 5, 27),
    (1, 1, 16),
    (8, 128, 8),
    (200, 3, 500),
    (17, 130, 64),    # C > one lane tile
    (64, 8, 1500),    # N > ROW_BLK: several grid tiles, heavy duplicates
]


@pytest.mark.parametrize("case", PS_APPLY_CASES)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_ps_apply_scatter_add(case, dtype):
    """Kernel must be BITWISE equal to np.add.at (same accumulation order)."""
    import contextlib
    R, C, N = case
    ctx = (jax.enable_x64(True) if dtype == np.float64
           else contextlib.nullcontext())
    with ctx:
        dense = RNG.normal(0, 1, (R, C)).astype(dtype)
        rows = RNG.integers(0, R, N).astype(np.int32)
        delta = RNG.normal(0, 1, (N, C)).astype(dtype)
        want = dense.copy()
        np.add.at(want, rows, delta)
        got = np.asarray(pk.scatter_add_pallas(
            jnp.asarray(dense), jnp.asarray(rows), jnp.asarray(delta),
            interpret=True))
        assert got.dtype == want.dtype
        assert np.array_equal(got, want)


def test_ps_apply_dummy_row_is_noop():
    """Sentinel index R routes padding to the dummy row, not real state."""
    R, C = 6, 4
    dense = np.asarray(RNG.normal(0, 1, (R, C)), np.float32)
    rows = np.array([0, R, 5, R], np.int32)
    delta = np.ones((4, C), np.float32)
    want = dense.copy()
    want[0] += 1
    want[5] += 1
    got = np.asarray(pk.scatter_add_pallas(
        jnp.asarray(dense), jnp.asarray(rows), jnp.asarray(delta),
        interpret=True))
    assert np.array_equal(got, want)


def test_ps_apply_long_batch_splits_in_submission_order(monkeypatch):
    """Batches longer than SMEM holds run as consecutive calls, bitwise."""
    monkeypatch.setattr(pk, "SMEM_ROWS", 16)
    R, C, N = 7, 3, 53
    dense = RNG.normal(0, 1, (R, C)).astype(np.float32)
    rows = RNG.integers(0, R, N).astype(np.int32)
    delta = RNG.normal(0, 1, (N, C)).astype(np.float32)
    want = dense.copy()
    np.add.at(want, rows, delta)
    got = np.asarray(pk.scatter_add_pallas(
        jnp.asarray(dense), jnp.asarray(rows), jnp.asarray(delta),
        interpret=True))
    assert np.array_equal(got, want)


def test_ps_apply_ref_duplicates():
    """jnp ref accumulates duplicates like np.add.at (integer-exact)."""
    dense = jnp.zeros((5, 3), jnp.float32)
    rows = jnp.asarray([1, 1, 1, 4], jnp.int32)
    delta = jnp.ones((4, 3), jnp.float32)
    out = np.asarray(pr.scatter_add(dense, rows, delta))
    assert np.array_equal(out[1], [3, 3, 3])
    assert np.array_equal(out[4], [1, 1, 1])
    assert np.array_equal(out[0], [0, 0, 0])


def test_ps_apply_ops_inplace_f64(monkeypatch):
    """Runtime entry keeps f64 bitwise through the interpret-mode kernel."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    from repro.kernels.ps_apply import ops as pops
    dense = RNG.normal(0, 1, (11, 7))
    rows = RNG.integers(0, 11, 23).astype(np.int64)
    delta = RNG.normal(0, 1, (23, 7))
    want = dense.copy()
    np.add.at(want, rows, delta)
    got = dense.copy()
    pops.scatter_add_inplace(got, rows, delta)
    assert got.dtype == np.float64
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# topk mag (largest-|Δ|-first ordering)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 6, 127, 128, 300])
def test_topk_mag_full_order(n):
    """Kernel ordering == stable descending argsort, incl. tie buckets."""
    mags = RNG.integers(0, max(2, n // 3), n).astype(np.float32)
    want = np.argsort(-mags, kind="stable")
    got = np.asarray(tk.topk_mag_pallas(jnp.asarray(mags), interpret=True))
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(tr.magnitude_order(jnp.asarray(mags))),
                          want)


def test_topk_mag_prefix_k():
    mags = np.asarray([0.5, 9.0, 1.0, 9.0, 3.0], np.float32)
    got = np.asarray(tk.topk_mag_pallas(jnp.asarray(mags), k=3,
                                        interpret=True))
    assert np.array_equal(got, [1, 3, 4])


def test_topk_mag_ops_matches_seed_sort(monkeypatch):
    """ops path == the seed Python sort key=-max|Δ| order (ties stable)."""
    from repro.kernels.topk_mag import ops as tops
    mags = RNG.integers(0, 4, 40).astype(np.float64)
    idx = list(range(len(mags)))
    idx.sort(key=lambda i: -mags[i])
    monkeypatch.setenv("REPRO_PALLAS", "off")
    assert np.array_equal(tops.magnitude_order(mags), idx)
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    assert np.array_equal(tops.magnitude_order(mags), idx)


def test_topk_mag_ops_refines_sub_f32_resolution_ties(monkeypatch):
    """Magnitudes distinct in f64 that collapse to one f32 value must still
    ship in exact f64 descending order: the kernel's f32 coarse pass alone
    would resolve them first-occurrence and diverge from the numpy path
    (send order feeds non-associative float applies — bitwise simulator
    conformance depends on it)."""
    from repro.kernels.topk_mag import ops as tops
    rng = np.random.default_rng(3)
    # perturbations far below f32 resolution at 1.0 (~6e-8): one f32 bucket
    sub = 1.0 + rng.permutation(8) * 1e-12
    assert np.unique(sub.astype(np.float32)).size == 1
    # mix in genuinely distinct values and an exact f64 tie inside the
    # bucket (index 8 duplicates one of the first eight values)
    mags = np.concatenate([sub, [sub[3], 2.0, 0.5, 7.0]])
    want = np.argsort(-mags, kind="stable")
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    got = tops.magnitude_order(mags)
    assert np.array_equal(got, want)
    # the exact f64 tie stays first-occurrence: original index before dup
    assert list(got).index(3) < list(got).index(8)
