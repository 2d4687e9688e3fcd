"""Tests of the SPMD consistency-sync layer (single-replica semantics here;
multi-device behaviour in test_distributed.py via subprocess)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policies
from repro.core.sync import (apply_and_sync, elastic_invariant_ok, force_sync,
                             init_sync_state, sync_trigger, tree_l2_norm,
                             tree_max_abs, vap_invariant_ok)


def _params():
    return {"w": jnp.zeros(4), "b": jnp.zeros(2)}


@functools.partial(jax.jit, static_argnames=("policy",))
def _step(p, s, u, policy):
    return apply_and_sync(p, s, u, policy, dp_axes=())


def test_bsp_syncs_every_step():
    p, s = _params(), init_sync_state(_params())
    for _ in range(3):
        p, s, synced = _step(p, s, {"w": jnp.ones(4) * .1, "b": jnp.ones(2) * .1},
                             policies.bsp())
        assert bool(synced)
        assert float(tree_max_abs(s.delta)) == 0.0


def test_cap_clock_period():
    p, s = _params(), init_sync_state(_params())
    pattern = []
    for _ in range(9):
        p, s, synced = _step(p, s, {"w": jnp.ones(4) * .01, "b": jnp.ones(2) * .01},
                             policies.cap(2))
        pattern.append(bool(synced))
    assert pattern == [False, False, True] * 3


def test_vap_value_trigger():
    pol = policies.vap(0.25)
    p, s = _params(), init_sync_state(_params())
    seen = []
    for _ in range(6):
        p, s, synced = _step(p, s, {"w": jnp.ones(4) * .1, "b": jnp.ones(2) * .1},
                             pol)
        seen.append(bool(synced))
        assert bool(vap_invariant_ok(pol, s))
    # 0.1 accumulates: .1 .2 .3>.25 -> sync at step 3, then period 3
    assert seen == [False, False, True, False, False, True]


def test_cvap_first_trigger_wins():
    pol = policies.cvap(5, 0.15)
    p, s = _params(), init_sync_state(_params())
    seen = []
    for _ in range(4):
        p, s, synced = _step(p, s, {"w": jnp.ones(4) * .1, "b": jnp.zeros(2)}, pol)
        seen.append(bool(synced))
    assert seen == [False, True, False, True]    # value fires before clock


def test_read_my_writes_params_updated_immediately():
    p, s = _params(), init_sync_state(_params())
    pol = policies.cap(5)
    p, s, synced = _step(p, s, {"w": jnp.ones(4), "b": jnp.ones(2)}, pol)
    assert not bool(synced)
    np.testing.assert_allclose(np.asarray(p["w"]), 1.0)   # visible pre-sync


def test_force_sync_resets():
    p, s = _params(), init_sync_state(_params())
    p, s, _ = _step(p, s, {"w": jnp.ones(4) * .1, "b": jnp.ones(2) * .1},
                    policies.cap(10))
    p2, s2 = force_sync(p, s, ())
    assert float(tree_max_abs(s2.delta)) == 0.0
    assert int(s2.steps_since_sync) == 0
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(p["w"]))


def test_oversized_update_admitted_bound_tracks_u():
    """A single |u| > v_thr is applied (max(u, v_thr) bound semantics)."""
    pol = policies.vap(0.1)
    p, s = _params(), init_sync_state(_params())
    p, s, synced = _step(p, s, {"w": jnp.ones(4) * 5.0, "b": jnp.zeros(2)}, pol)
    assert bool(synced)            # sync epoch triggers right away
    assert bool(vap_invariant_ok(pol, s))
    assert float(s.max_update_mag) == pytest.approx(5.0)


def test_essp_trigger_equals_ssp():
    """Under lockstep SPMD ESSP collapses to SSP: same clock trigger, step
    for step."""
    p1, s1 = _params(), init_sync_state(_params())
    p2, s2 = _params(), init_sync_state(_params())
    for _ in range(6):
        u = {"w": jnp.ones(4) * .01, "b": jnp.ones(2) * .01}
        p1, s1, t1 = _step(p1, s1, u, policies.ssp(2))
        p2, s2, t2 = _step(p2, s2, u, policies.essp(2))
        assert bool(t1) == bool(t2)
        np.testing.assert_array_equal(np.asarray(p1["w"]), np.asarray(p2["w"]))


def test_elastic_norm_trigger():
    """Elastic syncs when the accumulated drift's L2 norm would pass B, and
    the whole-accumulator invariant holds at every step."""
    pol = policies.elastic(0.25)
    p, s = _params(), init_sync_state(_params())
    seen = []
    for _ in range(6):
        # per-step delta norm = sqrt(6 * 0.1^2) ~ 0.245 <= B; two steps pass
        p, s, synced = _step(p, s, {"w": jnp.ones(4) * .1, "b": jnp.ones(2) * .1},
                             pol)
        seen.append(bool(synced))
        assert bool(elastic_invariant_ok(pol, s))
    assert seen == [False, True, False, True, False, True]


def test_elastic_oversized_update_bound_tracks_norm():
    """A single update with L2 norm > B is admitted; the invariant bound
    widens to max(max‖u‖₂, B) exactly as in the PS layers."""
    pol = policies.elastic(0.1)
    p, s = _params(), init_sync_state(_params())
    u = {"w": jnp.ones(4) * 5.0, "b": jnp.zeros(2)}
    p, s, synced = _step(p, s, u, pol)
    assert bool(synced)
    assert bool(elastic_invariant_ok(pol, s))
    assert float(s.max_update_l2) == pytest.approx(10.0)   # sqrt(4*25)


def test_tree_l2_norm():
    t = {"a": jnp.asarray([3.0, 0.0]), "b": jnp.asarray([[0.0, 4.0]])}
    assert float(tree_l2_norm(t)) == pytest.approx(5.0)


def test_trigger_uniform_with_trigger_axes_noop_single():
    pol = policies.vap(0.5)
    s = init_sync_state(_params())
    d = {"w": jnp.ones(4) * 0.6, "b": jnp.zeros(2)}
    t = sync_trigger(pol, s, d, dp_axes=(), trigger_axes=())
    assert bool(t)


# ---------------------------------------------------------------------------
# hierarchical + compressed paths on a real 2-pod mesh (8 fake devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hierarchical_pod_pending_no_double_count(devices8):
    """Integer per-replica updates on a (pod=2, data=4) mesh with
    hierarchy=3: every intermediate state must match the closed form
    'own-pod updates every epoch + peer-pod updates only at cross epochs' —
    any double counting of ``pod_pending`` across cross-pod epochs (or a
    missed reset) breaks the exact equality."""
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch import mesh as mesh_lib
from repro.core import policies, sync

mesh = mesh_lib.make_mesh((2, 4), ("pod", "data"))
R, HIER, T = 8, 3, 9
pol = policies.bsp()                     # sync epoch every step
x0 = {"w": jnp.zeros(4, jnp.float32)}

def local(p, s, u):
    sq = lambda t: jax.tree.map(lambda x: x[0], t)
    ex = lambda t: jax.tree.map(lambda x: x[None], t)
    p2, s2, _ = sync.apply_and_sync(sq(p), sq(s), sq(u), pol,
                                    dp_axes=("pod", "data"),
                                    hierarchy=HIER, pod_axis="pod")
    return ex(p2), ex(s2)

stack = lambda t: jax.tree.map(lambda x: jnp.stack([x] * R), t)
spec = lambda t: jax.tree.map(
    lambda x: P(("pod", "data"), *([None] * (x.ndim - 1))), t)
params = stack(x0)
state = stack(sync.init_sync_state(x0, hierarchy=HIER))
fn = jax.jit(jax.shard_map(
    local, mesh=mesh,
    in_specs=(spec(params), spec(state), spec(params)),
    out_specs=(spec(params), spec(state)), check_vma=False))

S_pod = [1.0 + 2 + 3 + 4, 5.0 + 6 + 7 + 8]   # per-step update mass per pod
for t in range(T):
    u = {"w": jnp.stack([jnp.full(4, float(r + 1), jnp.float32)
                         for r in range(R)])}
    params, state = fn(params, state, u)
    w = np.asarray(params["w"])              # (R, 4)
    crossed = 3 * ((t + 1) // HIER)          # epochs whose pend crossed pods
    for r in range(R):
        pod = r // 4
        want = S_pod[pod] * (t + 1) + S_pod[1 - pod] * crossed
        assert np.all(w[r] == want), (t, r, w[r], want)
    pend = np.asarray(state["pod_pending"]  # noqa: F821
                      if isinstance(state, dict) else state.pod_pending["w"])
    if (t + 1) % HIER == 0:
        assert np.all(pend == 0.0), (t, pend)   # reset after crossing
        assert np.all(w == w[0]), t             # pods fully agree
print("HIER_OK")
""")
    assert "HIER_OK" in out


@pytest.mark.slow
def test_bf16_error_feedback_keeps_drift_bounded(devices8):
    """compress='bf16' with fp32 error-feedback residual: after many syncs of
    bf16-unfriendly deltas the replicas must track the exact fp64 sum to
    ~one quantization step — not the T-times-larger drift a residual-free
    quantizer accumulates."""
    out = devices8("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch import mesh as mesh_lib
from repro.core import policies, sync

mesh = mesh_lib.make_mesh((2, 4), ("pod", "data"))
R, T = 8, 40
pol = policies.bsp()
x0 = {"w": jnp.zeros(4, jnp.float32)}

def local(p, s, u):
    sq = lambda t: jax.tree.map(lambda x: x[0], t)
    ex = lambda t: jax.tree.map(lambda x: x[None], t)
    p2, s2, _ = sync.apply_and_sync(sq(p), sq(s), sq(u), pol,
                                    dp_axes=("pod", "data"),
                                    compress="bf16")
    return ex(p2), ex(s2)

stack = lambda t: jax.tree.map(lambda x: jnp.stack([x] * R), t)
spec = lambda t: jax.tree.map(
    lambda x: P(("pod", "data"), *([None] * (x.ndim - 1))), t)
params = stack(x0)
state = stack(sync.init_sync_state(x0, compress="bf16"))
fn = jax.jit(jax.shard_map(
    local, mesh=mesh,
    in_specs=(spec(params), spec(state), spec(params)),
    out_specs=(spec(params), spec(state)), check_vma=False))

exact = np.zeros(4, dtype=np.float64)
max_res = 0.0
for t in range(T):
    vals = [0.001 * (r + 1) + 0.0001 * t for r in range(R)]  # bf16-unfriendly
    u = {"w": jnp.stack([jnp.full(4, v, jnp.float32) for v in vals])}
    exact += np.float64(np.asarray(u["w"])).sum(axis=0)
    params, state = fn(params, state, u)
    max_res = max(max_res, float(np.max(np.abs(np.asarray(state.residual["w"])))))

w = np.asarray(params["w"], dtype=np.float64)
drift = float(np.max(np.abs(w - exact[None, :])))
assert max_res > 0.0, "error-feedback residual never engaged"
# one bf16 quantization step of the per-sync send, NOT T of them
naive = T * 8 * 0.004 * 2 ** -8       # what residual-free drift would allow
assert drift < 2e-3 < naive * 10, (drift, naive)
# replicas agree up to their *current* residuals (each holds back its own
# not-yet-sent quantization error), never more
spread = float(np.max(np.abs(w - w[0])))
assert spread <= 2 * max_res + 1e-7, (spread, max_res)
print("BF16_OK", drift, max_res)
""")
    assert "BF16_OK" in out
