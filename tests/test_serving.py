"""Read-replica serving tier: per-read staleness SLOs measured against the
vector clock.

The contract under test is the *measured* stamp, not the request: every
:class:`ReadResult` carries the staleness actually observed against the
master shards' applied vector clocks (sampled after the serving copy, so
the stamp upper-bounds the truth), and these tests assert it never exceeds
the requested SLO — under free 4-worker interleavings for SSP, VAP, and
CVAP, over every serving transport, across mid-run replica joins, and
through master escalation.
"""
import itertools
import time

import numpy as np
import pytest

from repro.core import policies
from repro.runtime import FRESH, PSRuntime, ReadGateway, RuntimeConfig
from repro.runtime.serving import ReplicaSet

pytestmark = pytest.mark.serving


def _x0():
    return {"a": np.zeros((8, 4)), "b": np.zeros(5)}


def _fn(pause=0.0):
    def fn(w, clock, view, rng):
        if pause:
            time.sleep(pause)
        return {"a": rng.normal(0.0, 0.6, size=(8, 4)),
                "b": rng.normal(0.0, 0.6, size=5)}
    return fn


_POLICIES = [
    ("ssp3", policies.ssp(3)),
    ("vap", policies.vap(1.5)),
    ("cvap", policies.cvap(3, 1.5)),
]


# ---------------------------------------------------------------------------
# the core contract: measured <= requested, under free interleaving
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("polname,pol", _POLICIES, ids=[p[0] for p in _POLICIES])
def test_slo_honored_under_free_interleaving(polname, pol):
    """4 free-running workers, 200 clocks; the gateway serves a rotating
    mix of SLOs the whole run and every response's *measured* staleness —
    stamped against the live master vector clock — obeys the request."""
    rt = PSRuntime(RuntimeConfig(4, pol, _x0(), n_shards=2, threads_per_process=2, seed=7))
    rt.start(_fn(), 200, timeout=110)
    gw = ReadGateway(rt, n_replicas=2, transport="queue")
    slos = itertools.cycle([0, 2, 5, None])
    n = 0
    try:
        while rt.running:
            slo = next(slos)
            res = gw.read("a", slo=slo, timeout=5.0)
            bound = float("inf") if slo is None else slo
            assert res.staleness <= bound, (
                f"SLO violated: measured {res.staleness} > requested {slo} "
                f"(source {res.source})")
            assert res.staleness >= 0
            n += 1
            time.sleep(1e-3)       # pace the reader off the workers' GIL
        st = rt.wait()
        assert st.violations == [], st.violations[:5]
        # quiesced: a fresh-by-vc replica read equals the authoritative
        # master on every key (nothing was lost or double-applied on the
        # publish path)
        for key in ("a", "b"):
            res = gw.read(key, slo=0, timeout=15.0)
            np.testing.assert_array_equal(res.value, rt.master_value(key),
                                          err_msg=f"{polname} replica[{key}]")
            assert res.staleness == 0
        assert gw.replicas.violations == []
        assert gw.replicas.errors == []
        assert gw.stats.n_replica_reads > 0          # not all escalated
        assert n > 0
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# serving transports: queue + shm + tcp publish streams, >= 2 replicas
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("serving", ["queue", "shm", "tcp"])
def test_gateway_serves_over_transport(serving):
    """Two replicas fed over the given transport both serve reads; stamps
    obey the SLO mid-run and the replicas converge to the master exactly."""
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(3), _x0(), n_shards=2,
                   threads_per_process=2, seed=3))
    rt.start(_fn(pause=0.002), 60, timeout=90)
    gw = ReadGateway(rt, n_replicas=2, transport=serving)
    try:
        while rt.running:
            res = gw.read("a", slo=3, timeout=5.0)
            assert res.staleness <= 3
            time.sleep(1e-3)
        st = rt.wait()
        assert st.violations == []
        for key in ("a", "b"):
            res = gw.read(key, slo=0, timeout=15.0)
            np.testing.assert_array_equal(res.value, rt.master_value(key),
                                          err_msg=f"{serving} replica[{key}]")
        # both replicas participated (least-loaded routing alternates)
        for _ in range(4):
            gw.read("a", slo=0, timeout=15.0)
        assert set(gw.stats.reads_per_replica) == {0, 1}
        assert gw.replicas.violations == []
        assert gw.replicas.errors == []
    finally:
        gw.close()


def test_serving_over_multiprocess_runtime():
    """Forked clients over shm rings *and* a shm-fed replica tier: the
    write path and the read path share the transport machinery end to end."""
    rt = PSRuntime(RuntimeConfig(2, policies.ssp(3), _x0(), n_shards=2,
                   threads_per_process=1, seed=5, transport="proc"))
    rt.start(_fn(pause=0.002), 40, timeout=120)
    gw = ReadGateway(rt, n_replicas=2, transport="shm")
    try:
        while rt.running:
            res = gw.read("a", slo=3, timeout=5.0)
            assert res.staleness <= 3
            time.sleep(1e-3)
        st = rt.wait()
        assert st.violations == []
        res = gw.read("a", slo=0, timeout=15.0)
        np.testing.assert_array_equal(res.value, rt.master_value("a"))
        assert gw.replicas.errors == []
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# fresh reads + escalation
# ---------------------------------------------------------------------------


def test_fresh_reads_escalate_to_master():
    rt = PSRuntime(RuntimeConfig(2, policies.ssp(2), _x0(), n_shards=2, seed=1))
    rt.start(_fn(pause=0.002), 30, timeout=60)
    gw = ReadGateway(rt, n_replicas=1, transport="queue")
    try:
        saw_master = 0
        while rt.running:
            res = gw.read("a", slo=FRESH, timeout=5.0)
            assert res.source == "master"
            assert res.staleness == 0
            saw_master += 1
        rt.wait()
        assert saw_master > 0
        assert gw.stats.n_master_reads == saw_master
    finally:
        gw.close()


def test_unattainable_slo_escalates_to_master():
    """A replica pinned behind the master frontier cannot satisfy slo=0:
    the gateway parks on the doorbell, hits the deadline, and escalates —
    the response is the master value, stamped staleness 0."""
    rt = PSRuntime(RuntimeConfig(2, policies.ssp(2), _x0(), n_shards=2, seed=2))
    # subscribe before start: the shards process the Subscribe when their
    # threads come up, and the replica ingests the whole run
    gw = ReadGateway(rt, n_replicas=1, transport="queue")
    rt.run(_fn(), 10, timeout=60)
    try:
        rep = gw.replicas.replicas[0]
        # let the replica catch up first, then pin it behind the frontier
        res = gw.read("a", slo=0, timeout=10.0)
        assert res.escalated is False
        with rep.lock:
            rep.vc -= 3
        res = gw.read("a", slo=0, timeout=0.6)
        assert res.escalated is True
        assert res.source == "master"
        assert res.staleness == 0
        np.testing.assert_array_equal(res.value, rt.master_value("a"))
        assert gw.stats.n_escalations == 1
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# mid-run join: snapshot bootstrap + in-stream state
# ---------------------------------------------------------------------------


def test_replica_joins_mid_run_equals_master_at_quiesce(tmp_path):
    """A replica added mid-run — warm-started from the latest periodic
    snapshot, corrected by the shards' in-stream bootstrap states — holds
    exactly the master state once the runtime quiesces."""
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(3), _x0(), n_shards=2,
                   threads_per_process=2, seed=9, snapshot_every=5,
                   snapshot_dir=str(tmp_path)))
    rt.start(_fn(pause=0.002), 40, timeout=120)
    gw = ReadGateway(rt, n_replicas=1, transport="queue")
    try:
        # wait until a periodic snapshot exists, then join
        deadline = time.monotonic() + 60
        while rt.latest_snapshot() is None and rt.running:
            assert time.monotonic() < deadline
            time.sleep(2e-3)
        assert rt.latest_snapshot() is not None
        joined = gw.add_replica(bootstrap_from_snapshot=True)
        while rt.running:
            res = gw.read("a", slo=4, timeout=5.0)
            assert res.staleness <= 4
            time.sleep(1e-3)
        st = rt.wait()
        assert st.violations == []
        # force the joined replica to full catch-up via the vc, then
        # compare raw buffers (not just a routed read)
        deadline = time.monotonic() + 15
        rset = gw.replicas
        while rset.staleness(joined.vc, rset.master_vc()) > 0:
            assert time.monotonic() < deadline, "joined replica never caught up"
            time.sleep(5e-3)
        for key in ("a", "b"):
            value, _ = joined.serve(key)
            np.testing.assert_array_equal(
                value.reshape(rt._shapes[key]),
                rt.master_value(key), err_msg=f"joined replica[{key}]")
        assert gw.replicas.violations == []
        assert gw.replicas.errors == []
    finally:
        gw.close()


def test_f32_replica_seeded_from_snapshot_equals_master(tmp_path):
    """A float32 table: a replica warm-started from a periodic snapshot
    keeps float32 buffers and, with non-integer deltas, holds exactly the
    master's float32 state at quiesce (no float64 drift)."""
    x0 = {k: v.astype(np.float32) + np.float32(0.1) for k, v in _x0().items()}
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(3), x0, n_shards=2,
                   threads_per_process=2, seed=13, snapshot_every=4,
                   snapshot_dir=str(tmp_path)))
    rt.start(_fn(pause=0.002), 30, timeout=120)
    gw = ReadGateway(rt, n_replicas=1, transport="queue")
    try:
        deadline = time.monotonic() + 60
        while rt.latest_snapshot() is None and rt.running:
            assert time.monotonic() < deadline
            time.sleep(2e-3)
        assert rt.latest_snapshot() is not None
        joined = gw.add_replica(bootstrap_from_snapshot=True)
        assert all(v.dtype == np.float32 for v in joined.values.values())
        st = rt.wait()
        assert st.violations == []
        deadline = time.monotonic() + 15
        rset = gw.replicas
        while rset.staleness(joined.vc, rset.master_vc()) > 0:
            assert time.monotonic() < deadline, "joined replica never caught up"
            time.sleep(5e-3)
        for key in ("a", "b"):
            value, _ = joined.serve(key)
            master = rt.master_value(key)
            assert value.dtype == master.dtype == np.float32
            np.testing.assert_array_equal(
                value.reshape(rt._shapes[key]), master,
                err_msg=f"joined replica[{key}]")
        assert gw.replicas.errors == []
    finally:
        gw.close()


def test_poisoned_replica_leaves_the_rotation():
    """A replica whose ingest raised can no longer guarantee its vector
    clock covers its values: the gateway must never route to it again
    (values would be stamped fresher than they are)."""
    rt = PSRuntime(RuntimeConfig(2, policies.ssp(2), _x0(), n_shards=2, seed=4))
    gw = ReadGateway(rt, n_replicas=2, transport="queue")
    rt.run(_fn(), 6, timeout=60)
    try:
        rep0 = gw.replicas.replicas[0]

        class Bogus:                       # not a publish message type
            shard = 0
            seq = 10 ** 6

        rep0.inbox.put(Bogus())
        deadline = time.monotonic() + 10
        while not rep0.poisoned:
            assert time.monotonic() < deadline, "ingest error not recorded"
            time.sleep(2e-3)
        assert gw.replicas.errors != []
        for _ in range(4):
            res = gw.read("a", slo=0, timeout=10.0)
            assert res.source == "replica:1", res.source
            np.testing.assert_array_equal(res.value, rt.master_value("a"))
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------


def test_gateway_rejects_bad_slo_and_transport():
    rt = PSRuntime(RuntimeConfig(2, policies.bsp(), _x0(), n_shards=2))
    with pytest.raises(ValueError, match="serving transport"):
        ReplicaSet(rt, 1, transport="carrier-pigeon")
    with pytest.raises(ValueError, match="replica"):
        ReplicaSet(rt, 0)
    gw = ReadGateway(rt, n_replicas=1, transport="queue")
    try:
        with pytest.raises(ValueError, match="slo"):
            gw.read("a", slo=-1)
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# replica ingest backpressure: drop-and-resync (ROADMAP follow-up)
# ---------------------------------------------------------------------------


def test_wedged_replica_never_stalls_publish_and_resyncs():
    """A deliberately wedged replica (its ring reader paused) must not
    stall the shard's publish write: the shard drops its frames once the
    tiny ring fills (pub_drops > 0), keeps applying updates at full speed,
    and re-bootstraps the replica with a fresh in-stream state cut once the
    ring drains — after which the replica equals the master exactly."""
    def fn(w, clock, view, rng):
        time.sleep(1e-3)
        return {"a": rng.normal(0.0, 0.6, size=(8, 4))}

    rt = PSRuntime(RuntimeConfig(2, policies.ssp(3), {"a": np.zeros((8, 4))}, n_shards=2,
                   seed=0))
    rt.start(fn, 400, timeout=110)
    rset = ReplicaSet(rt, n_replicas=2, transport="shm", ring_capacity=1)
    try:
        time.sleep(0.1)
        rset.wedge(0, True)
        deadline = time.monotonic() + 60
        while rt.running and rset.pub_drops == 0:
            assert time.monotonic() < deadline, "wedged ring never filled"
            time.sleep(0.005)
        assert rset.pub_drops > 0, "publish should have dropped, not blocked"
        assert 0 in rset.stale_replicas
        rset.wedge(0, False)
        while rt.running and rset.pub_resyncs == 0:
            assert time.monotonic() < deadline, "recovery resync never came"
            time.sleep(0.005)
        st = rt.wait()
        assert st.violations == [], st.violations[:5]
        assert rset.pub_resyncs > 0
        assert rset.errors == [] and rset.violations == []
        time.sleep(0.5)                    # final publish cycles drain
        assert 0 not in rset.stale_replicas
        for rep in rset.replicas:
            assert not rep.poisoned
            v, _ = rep.serve("a")
            np.testing.assert_allclose(
                v, rt.master_value("a").reshape(v.shape), atol=1e-9,
                err_msg=f"replica {rep.rid} did not recover exactly")
    finally:
        rset.close()
        if rt.running:
            rt.wait()
