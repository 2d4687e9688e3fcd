"""Elastic shard membership: live re-partitioning under load.

The contract (ISSUE 5 / ROADMAP "elastic shard membership"): shards can be
added and removed **mid-run**, with

  (a) the final state bitwise-equal to the simulator spec on deterministic
      schedules — membership change is invisible to the update algebra;
  (b) the SSP clock bound and VAP value bound holding for accesses issued
      *during* the migration window (check_invariants records every
      mid-run violation, so ``stats.violations == []`` covers the window);
  (c) zero lost or duplicated updates, by per-process counter audit
      (parts sent by each client == parts applied across all shard slots);

for all three transports — in-process queues, forked clients over shm
rings, and tcp loopback — plus serving-tier re-subscription with in-stream
re-bootstrap, down-to-one-shard shrink, slot re-activation, and the
scriptable :class:`MembershipPlan`.
"""
import time

import numpy as np
import pytest

from repro.core import AsyncPS, NetworkModel, policies
from repro.runtime import MembershipPlan, PSRuntime, ReadGateway, RuntimeConfig

from chaos import assert_counters, det_fn, expected_final, x0

pytestmark = pytest.mark.membership

_POLICIES = [
    ("ssp2", policies.ssp(2)),
    ("vap", policies.vap(4.5)),
    ("cvap_strong", policies.cvap(2, 4.5, strong=True)),
]


def _wait_clock(rt, clock, budget=30.0):
    deadline = time.monotonic() + budget
    while rt.completed_clock() < clock:
        assert time.monotonic() < deadline, "runtime stalled before trigger"
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# the core contract: add + remove mid-run == simulator, per policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("polname,pol", _POLICIES, ids=[p[0] for p in _POLICIES])
def test_add_and_remove_mid_run_equals_simulator(polname, pol):
    """Free 4-worker interleaving; a shard joins at clock >= 5 and the
    original shard 0 retires at clock >= 12.  Final master and every
    process cache equal the (membership-free) simulator bitwise; mid-run
    clock/value bound checks and the update-counter audit record zero
    violations across the migration windows."""
    seed = 3
    fn = det_fn(seed)
    sim = AsyncPS(4, pol, x0(), threads_per_process=2, seed=seed,
                  network=NetworkModel(seed=seed))
    st_sim = sim.run(fn, 24)

    rt = PSRuntime(RuntimeConfig(4, pol, x0(), n_shards=2, threads_per_process=2,
                   seed=seed, max_shards=4))
    rt.start(fn, 24, timeout=90)
    _wait_clock(rt, 5)
    sid = rt.add_shard()
    assert sid == 2 and rt.partition.active == (0, 1, 2)
    _wait_clock(rt, 12)
    rt.remove_shard(0)
    assert rt.partition.active == (1, 2)
    st_rt = rt.wait()

    assert st_sim.violations == [], st_sim.violations
    assert st_rt.violations == [], st_rt.violations[:5]
    assert st_sim.n_updates == st_rt.n_updates
    assert_counters(rt)
    if pol.clock_bounded:
        assert st_rt.max_observed_staleness <= pol.staleness
    for k, ref in sim.views[0].items():
        np.testing.assert_array_equal(
            rt.master_value(k).reshape(ref.shape), ref,
            err_msg=f"{polname} master[{k}]")
        for p in range(rt.n_proc):
            np.testing.assert_array_equal(
                rt.view(p)[k].reshape(ref.shape), ref,
                err_msg=f"{polname} proc{p}[{k}]")


# ---------------------------------------------------------------------------
# all transports: the epoch barrier works over real wires
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transport", ["queue", "shm", "tcp"])
def test_membership_all_transports(transport):
    """Scripted add + remove mid-run under every transport (forked OS
    clients for shm/tcp): the epoch announce/ack barrier rides the same
    FIFO channels as updates, rows migrate parent-side through the
    vc-stamped snapshot re-partition path, and the quiesced state is
    bitwise x0 + sum(updates) with a clean counter audit."""
    seed = 0
    n_clocks = 22
    plan = MembershipPlan.parse([(4, "add", 2), (10, "remove", 0)])
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(2), x0(), n_shards=2,
                   threads_per_process=2, seed=seed, max_shards=3,
                   transport=transport, membership_plan=plan))
    st = rt.run(det_fn(seed), n_clocks, timeout=110)
    assert st.violations == [], st.violations[:5]
    assert [r for _, r in plan.results] == ["ok", "ok"], plan.results
    assert rt.partition.active == (1, 2)
    assert st.n_updates == 4 * n_clocks * 2
    exp = expected_final(seed, 4, n_clocks)
    for k, ref in exp.items():
        np.testing.assert_array_equal(
            rt.master_value(k).reshape(ref.shape), ref,
            err_msg=f"{transport} master[{k}]")
    if transport == "queue":
        assert_counters(rt)
    else:
        # proc mode: the per-client sent counters were shipped back over
        # the pipes and checked in _final_checks (violations above); the
        # parent-side applied counters must cover every update part
        applied = int(sum(s.applied_parts.sum() for s in rt.shards))
        assert applied == int(rt._parts_sent.sum()) > 0


@pytest.mark.parametrize("transport", ["queue", "shm", "tcp"])
def test_plan_op_at_final_clock_runs_before_quiesce(transport):
    """A scripted op whose clock the run reaches only once every worker
    has finished still runs, during the quiesce: forked clients keep
    listening (shards hold their fins for the plan) so the epoch barrier
    completes, and an event past the last clock is skipped, not waited
    for."""
    seed = 5
    n_clocks = 8
    plan = MembershipPlan.parse([(n_clocks, "add", 2),
                                 (n_clocks + 1, "remove", 0)])
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(2), x0(), n_shards=2,
                   threads_per_process=2, seed=seed, max_shards=3,
                   transport=transport, membership_plan=plan))
    st = rt.run(det_fn(seed), n_clocks, timeout=60)
    assert st.violations == [], st.violations[:5]
    assert [r for _, r in plan.results] == ["ok", "skipped"], plan.results
    assert rt.partition.active == (0, 1, 2)
    for k, ref in expected_final(seed, 4, n_clocks).items():
        np.testing.assert_array_equal(
            rt.master_value(k).reshape(ref.shape), ref,
            err_msg=f"{transport} master[{k}]")


# ---------------------------------------------------------------------------
# shrink to one, grow back, re-activate a retired slot
# ---------------------------------------------------------------------------


def test_shrink_to_one_shard_and_reactivate():
    """Remove down to a single shard (everything migrates onto it), then
    re-activate a previously retired slot — the stale-marker epoch filter
    and the seeded frontier markers must keep the clock bound live across
    the re-activation."""
    seed = 5
    rt = PSRuntime(RuntimeConfig(2, policies.ssp(1), x0(), n_shards=3,
                   threads_per_process=1, seed=seed, max_shards=3))
    rt.start(det_fn(seed), 30, timeout=90)
    _wait_clock(rt, 4)
    rt.remove_shard(0)
    rt.remove_shard(2)
    assert rt.partition.active == (1,)
    _wait_clock(rt, 12)
    rt.add_shard(0)                       # re-activate the retired slot 0
    assert rt.partition.active == (0, 1)
    st = rt.wait()
    assert st.violations == [], st.violations[:5]
    assert_counters(rt)
    exp = expected_final(seed, 2, 30)
    for k, ref in exp.items():
        np.testing.assert_array_equal(rt.master_value(k).reshape(ref.shape),
                                      ref)
    assert rt.membership.log == [(1, (1, 2)), (2, (1,)), (3, (0, 1))]


def test_membership_op_validation():
    rt = PSRuntime(RuntimeConfig(2, policies.ssp(1), x0(), n_shards=2, seed=0,
                   max_shards=3))
    with pytest.raises(RuntimeError, match="running"):
        rt.add_shard()                    # not started yet
    rt.start(det_fn(0), 12, timeout=60)
    try:
        _wait_clock(rt, 2)
        with pytest.raises(ValueError, match="already active"):
            rt.add_shard(0)
        with pytest.raises(ValueError, match="not active"):
            rt.remove_shard(2)
        rt.add_shard()                    # 3 active: slots exhausted
        with pytest.raises(ValueError, match="max_shards"):
            rt.add_shard()
        rt.remove_shard(1)
        rt.remove_shard(2)
        with pytest.raises(ValueError, match="last active"):
            rt.remove_shard(0)
    finally:
        st = rt.wait()
    assert st.violations == [], st.violations[:5]


def test_max_shards_validation():
    with pytest.raises(ValueError, match="max_shards"):
        PSRuntime(RuntimeConfig(2, policies.bsp(), x0(), n_shards=3, max_shards=2))


# ---------------------------------------------------------------------------
# serving tier across membership: SLO stamps honored, re-bootstrap exact
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_serving_slo_honored_across_membership_change():
    """Gateway reads under SLOs run *through* an add and a remove: every
    response's measured staleness obeys the request (the master frontier
    includes the new owner from install, so mid-migration reads park or
    escalate rather than stamp optimistically), and after quiesce every
    replica equals the master bitwise — the in-stream re-bootstrap made the
    migrated rows exact."""
    seed = 9
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(3), x0(), n_shards=2,
                   threads_per_process=2, seed=seed, max_shards=3))
    rt.start(det_fn(seed), 60, timeout=110)
    gw = ReadGateway(rt, n_replicas=2, transport="queue")
    bad = []
    import itertools
    import threading
    stop = threading.Event()

    def reader():
        slos = itertools.cycle([0, 2, 5, None])
        keys = itertools.cycle(["a", "b"])
        while not stop.is_set():
            slo = next(slos)
            res = gw.read(next(keys), slo=slo, timeout=10.0)
            if slo is not None and res.staleness > slo:
                bad.append((slo, res.staleness, res.source))

    th = threading.Thread(target=reader, daemon=True)
    th.start()
    try:
        _wait_clock(rt, 8)
        rt.add_shard()
        _wait_clock(rt, 25)
        rt.remove_shard(1)
        st = rt.wait()
    finally:
        stop.set()
        th.join(timeout=10.0)
    assert st.violations == [], st.violations[:5]
    assert bad == [], bad[:5]
    assert gw.stats.n_reads > 0
    assert gw.replicas.errors == []
    time.sleep(0.3)                       # let the final publish cycle land
    for rep in gw.replicas.replicas:
        assert not rep.poisoned
        for k, ref in expected_final(seed, 4, 60).items():
            v, _ = rep.serve(k)
            np.testing.assert_array_equal(v.reshape(ref.shape), ref,
                                          err_msg=f"replica{rep.rid}[{k}]")
    gw.close()


# ---------------------------------------------------------------------------
# snapshots interleaved with membership
# ---------------------------------------------------------------------------


def test_snapshot_during_membership_reflects_current_partition():
    """A snapshot taken after a membership change captures the *active*
    shards of the new epoch, restores into any shard count, and its vc
    stamps stay internally consistent (validate_vcs passes on load)."""
    from repro.runtime import snapshot_params, validate_vcs

    seed = 11
    rt = PSRuntime(RuntimeConfig(2, policies.ssp(2), x0(), n_shards=2,
                   threads_per_process=1, seed=seed, max_shards=3))
    rt.start(det_fn(seed), 20, timeout=90)
    _wait_clock(rt, 4)
    rt.add_shard()
    snap_mid = rt.snapshot()              # mid-run, 3 active shards
    validate_vcs(snap_mid)
    assert snap_mid["n_shards"] == 3 and len(snap_mid["shards"]) == 3
    st = rt.wait()
    assert st.violations == [], st.violations[:5]
    snap = rt.snapshot()
    params = snapshot_params(snap)
    for k, ref in expected_final(seed, 2, 20).items():
        np.testing.assert_array_equal(params[k].reshape(ref.shape), ref)
    # restorable into a different shard count (re-partition path)
    rt2 = PSRuntime(RuntimeConfig(2, policies.bsp(), x0(), n_shards=4, restore_from=snap))
    for k, ref in expected_final(seed, 2, 20).items():
        np.testing.assert_array_equal(rt2.master_value(k).reshape(ref.shape),
                                      ref)
