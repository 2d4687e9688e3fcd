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
    # b, sq, skv, kvh, G, dh, dv, window, cap
    (2, 256, 256, 2, 2, 64, 64, None, None),
    (2, 256, 256, 2, 2, 64, 64, 100, None),
    (1, 300, 300, 1, 4, 32, 32, None, 50.0),
    (1, 128, 128, 4, 1, 192, 128, None, None),     # MLA: dv != dh
    (1, 512, 512, 1, 1, 128, 128, 64, 30.0),       # window + cap
    (2, 64, 512, 2, 2, 64, 64, None, None),        # q is a suffix (prefill tail)
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dtype):
    b, sq, skv, kvh, G, dh, dv, window, cap = case
    q = jnp.asarray(RNG.normal(0, 1, (b, sq, kvh, G, dh)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, skv, kvh, dh)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, skv, kvh, dv)), dtype)
    qp = jnp.arange(skv - sq, skv, dtype=jnp.int32)
    kp = jnp.arange(skv, dtype=jnp.int32)
    out = fk.flash_attention_pallas(q, k, v, qp, kp, window=window, cap=cap,
                                    interpret=True)
    ref = fr.attention(q, k, v, qp, kp, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_matches_model_chunked_core():
    """kernel == ref == the model-side banded chunked core."""
    from repro.models.attention import attention_core
    b, s, kvh, G, dh = 1, 1024, 2, 1, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, s, kvh, G, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, kvh, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, kvh, dh)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    for window in (None, 128):
        a = fk.flash_attention_pallas(q, k, v, pos, pos, window=window,
                                      interpret=True)
        c = attention_core(q, k, v, pos, pos, window=window, chunk=256)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=5e-5)


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
