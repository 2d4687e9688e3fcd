"""Durability tier: per-shard write-ahead delta log + exact-clock recovery.

Three layers under test:

* **WalWriter / read_segment** (repro.runtime.wal) — the vc-stamped
  append/group-commit wire format on disk: roundtrip, torn-tail recovery
  to the last complete record, segment rotation, seal/reopen naming,
  covered-prefix pruning.
* **UidDedup** (repro.runtime.shard) — the cross-epoch uid-level dedup
  table that makes at-least-once replay idempotent, unit-tested standalone.
* **recover_to_vc** (repro.runtime.snapshot) — ``snapshot + replay(log,
  upto_vc)``: genesis replay, snapshot-positioned replay, point-in-time
  restore, double-replay idempotence, tampered-stamp refusal, retention.

The end-to-end legs assert the durability audit exactly: recovered
``applied_parts`` equals the runtime's per-process parts-sent counters
(zero lost/duplicated updates) and the recovered state is **bitwise**
equal to the live master (integer deltas: f64 sums are exact and
order-independent).
"""
import os

import numpy as np
import pytest

from repro.core import policies
from repro.runtime import (PSRuntime, RuntimeConfig, UidDedup, UpdateMsg,
                           WalWriter, prune_segments, read_segment,
                           recover_to_vc, wal_segments)
from repro.runtime.snapshot import load_snapshot, save_snapshot
from repro.runtime.transport import RowCodec


def _x0():
    return {"a": np.arange(32, dtype=float).reshape(8, 4) / 2.0,
            "b": np.ones(5)}


def _fn(seed):
    def fn(w, clock, view, rng):
        r = np.random.default_rng((seed, w, clock))
        return {"a": r.integers(-3, 4, size=(8, 4)).astype(float),
                "b": r.integers(-3, 4, size=5).astype(float)}
    return fn


def _expected(seed, n_workers, n_clocks, upto_ts=None):
    out = {k: v.astype(float) for k, v in _x0().items()}
    fn = _fn(seed)
    last = n_clocks if upto_ts is None else min(n_clocks, upto_ts + 1)
    for w in range(n_workers):
        for c in range(last):
            for k, d in fn(w, c, None, None).items():
                out[k] = out[k] + d
    return out


def _run(tmp_path, seed=5, n_clocks=12, **cfg):
    wal_dir = str(tmp_path / "wal")
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(3), _x0(), n_shards=2,
                                 threads_per_process=2, seed=seed,
                                 wal_dir=wal_dir, **cfg))
    rt.run(_fn(seed), n_clocks=n_clocks)
    return rt, wal_dir


def _msg(uid, process, ts, key="a", rows=(0, 1), val=1.0):
    rows = np.asarray(rows, dtype=np.int64)
    cols = 4 if key == "a" else 1
    delta = np.full((len(rows), cols), float(val), dtype=np.float64)
    return UpdateMsg(uid=uid, worker=process, process=process, ts=ts,
                     key=key, rows=rows, delta=delta)


def _codec():
    return RowCodec(list(_x0().keys()))


# ---------------------------------------------------------------------------
# WalWriter / read_segment
# ---------------------------------------------------------------------------


def test_writer_reader_roundtrip(tmp_path):
    w = WalWriter(str(tmp_path), sid=0, codec=_codec(), n_proc=2)
    w.log_parts([_msg(1, 0, 0), _msg(2, 1, 0, key="b", rows=[3])])
    w.commit(np.array([0, 0]))
    w.log_parts([_msg(3, 0, 1, val=-2.5)])
    w.seal(np.array([1, 0]))
    segs = wal_segments(str(tmp_path))
    assert list(segs) == [0] and len(segs[0]) == 1
    (start, path), = segs[0]
    assert start == 0
    records, sealed = read_segment(path, _codec())
    assert sealed
    kinds = [k for k, _ in records]
    assert kinds == ["parts", "vc", "parts", "vc"]
    parts = [m for k, run in records if k == "parts" for m in run]
    assert [(m.uid, m.process, m.ts, m.key) for m in parts] == [
        (1, 0, 0, "a"), (2, 1, 0, "b"), (3, 0, 1, "a")]
    np.testing.assert_array_equal(parts[2].delta,
                                  np.full((2, 4), -2.5))
    stamps = [np.asarray(v.clock_vc) for k, v in records if k == "vc"]
    assert stamps[0].tolist() == [0, 0] and stamps[1].tolist() == [1, 0]
    marks = w.marks()
    assert marks["parts"] == 3
    assert marks["applied"].tolist() == [2, 1]
    assert marks["max_ts"].tolist() == [1, 0]


def test_torn_tail_recovers_to_last_complete_record(tmp_path):
    """A segment truncated at ANY byte offset (simulated torn write) decodes
    cleanly to a prefix of the full record stream — never raises, never
    yields a phantom record."""
    w = WalWriter(str(tmp_path), sid=0, codec=_codec(), n_proc=2)
    for i in range(4):
        w.log_parts([_msg(2 * i, 0, i), _msg(2 * i + 1, 1, i, key="b",
                                             rows=[i])])
        w.commit(np.array([i, i]))
    w.seal()
    (_, path), = wal_segments(str(tmp_path))[0]
    full, sealed = read_segment(path, _codec())
    assert sealed
    data = open(path, "rb").read()
    torn = str(tmp_path / "torn.bin")
    prev_len = -1
    for cut in range(len(data) - 1, -1, -1):
        with open(torn, "wb") as f:
            f.write(data[:cut])
        records, sealed = read_segment(torn, _codec())
        assert not sealed                    # the EOF sentinel is gone
        assert len(records) <= len(full)
        for (k, v), (fk, fv) in zip(records, full):
            assert k == fk                   # a strict prefix, record-wise
        assert len(records) <= max(prev_len, len(full))
        prev_len = len(records)


def test_data_after_eof_is_corruption(tmp_path):
    w = WalWriter(str(tmp_path), sid=0, codec=_codec(), n_proc=2)
    w.log_parts([_msg(1, 0, 0)])
    w.seal(np.array([0, 0]))
    (_, path), = wal_segments(str(tmp_path))[0]
    with open(path, "ab") as f:
        f.write(b"\x00\x01\x02\x03")
    with pytest.raises(ValueError, match="data after EOF"):
        read_segment(path, _codec())


def test_segment_rotation_positions_are_contiguous(tmp_path):
    """Tiny segment_bytes forces rotation on nearly every commit; segment
    start positions must tile the slot's log exactly."""
    w = WalWriter(str(tmp_path), sid=3, codec=_codec(), n_proc=2,
                  segment_bytes=64)
    n = 0
    for i in range(10):
        w.log_parts([_msg(i, i % 2, i)])
        n += 1
        w.commit(np.array([i, i]))
    w.seal()
    segs = wal_segments(str(tmp_path))[3]
    assert len(segs) > 1
    pos = 0
    for start, path in segs:
        assert start == pos
        records, sealed = read_segment(path, _codec())
        assert sealed
        pos += sum(len(run) for k, run in records if k == "parts")
    assert pos == n


def test_seal_reopen_names_never_collide(tmp_path):
    """Seal with zero new parts, then write again (kill + rejoin of a slot):
    the generation counter keeps segment names distinct, so the reopened
    log never appends past an EOF sentinel."""
    w = WalWriter(str(tmp_path), sid=0, codec=_codec(), n_proc=2)
    w.log_parts([_msg(1, 0, 0)])
    w.seal(np.array([0, 0]))
    w.seal(np.array([0, 0]))                  # idempotent no-op
    w.log_parts([_msg(2, 0, 1)])              # re-activation, 0 new parts
    w.seal(np.array([1, 0]))                  # before: same start_part=1
    w.log_parts([_msg(3, 1, 0)])
    w.seal(np.array([1, 0]))
    segs = wal_segments(str(tmp_path))[0]
    assert len(segs) == 3
    assert len({path for _, path in segs}) == 3
    for _, path in segs:
        records, sealed = read_segment(path, _codec())   # none raises
        assert sealed


def test_prune_segments_keeps_uncovered_and_last(tmp_path):
    w = WalWriter(str(tmp_path), sid=0, codec=_codec(), n_proc=2,
                  segment_bytes=1)            # rotate every commit
    for i in range(5):
        w.log_parts([_msg(i, 0, i)])
        w.commit(np.array([i, 0]))
    w.seal()
    segs = wal_segments(str(tmp_path))[0]
    assert [s for s, _ in segs] == [0, 1, 2, 3, 4]
    removed = prune_segments(str(tmp_path), {0: 3})
    assert len(removed) == 3                  # segments [0,1) [1,2) [2,3)
    left = wal_segments(str(tmp_path))[0]
    assert [s for s, _ in left] == [3, 4]
    # covering everything still never deletes the last segment
    prune_segments(str(tmp_path), {0: 10 ** 9})
    assert [s for s, _ in wal_segments(str(tmp_path))[0]] == [4]


# ---------------------------------------------------------------------------
# UidDedup (standalone unit — the cross-epoch apply-path dedup table)
# ---------------------------------------------------------------------------


def test_uid_dedup_drops_duplicates_and_prunes_on_advance():
    d = UidDedup(2)
    assert d.fresh(10, 0, 0)
    assert not d.fresh(10, 0, 0)              # exact duplicate
    assert d.n_dropped == 1
    assert d.fresh(11, 0, 1)
    assert d.fresh(20, 1, 0)                  # other process: independent
    d.advance(0, 0)                           # clock 0 complete for proc 0
    assert d.frontier.tolist() == [0, -1]
    assert not d.fresh(12, 0, 0)              # late duplicate below frontier
    assert d.fresh(13, 0, 1)                  # ts above frontier: fresh
    assert 10 not in d._seen[0]               # pruned (covered by frontier)
    assert 20 in d._seen[1]                   # other process untouched
    d.advance(0, -5)                          # never regresses
    assert d.frontier.tolist() == [0, -1]


def test_uid_dedup_cross_epoch_resend():
    """The kill-epoch scenario: a part applied before the cut is resent
    after it (same uid, same ts) — dropped whether or not a ClockMsg
    advanced the frontier in between."""
    d = UidDedup(2)
    assert d.fresh(7, 1, 3)
    assert not d.fresh(7, 1, 3)               # resend before any boundary
    d.advance(1, 3)
    assert not d.fresh(7, 1, 3)               # resend after the boundary
    assert d.n_dropped == 2


# ---------------------------------------------------------------------------
# recover_to_vc: snapshot + replay(log, upto_vc)
# ---------------------------------------------------------------------------


def test_genesis_recovery_bitwise_and_audit(tmp_path):
    rt, wal_dir = _run(tmp_path, seed=5, n_clocks=12)
    rec = recover_to_vc(_x0(), wal_dir)
    assert rec["from_snapshot"] is None
    assert rec["applied_parts"].tolist() == rt._parts_sent.tolist()
    assert rec["n_deduped"] == 0
    assert rec["clock"] == 12
    exp = _expected(5, 4, 12)
    for k, v in exp.items():
        np.testing.assert_array_equal(rec["params"][k], v)


def test_snapshot_positioned_replay(tmp_path):
    """With periodic snapshots on, recovery seeds from the newest snapshot
    and replays only the per-slot log suffix beyond its positional marks —
    same bitwise result, same audit."""
    rt, wal_dir = _run(tmp_path, seed=6, n_clocks=15, snapshot_every=4,
                       snapshot_dir=str(tmp_path / "snaps"))
    rec = recover_to_vc(_x0(), wal_dir,
                        snapshot_dir=str(tmp_path / "snaps"))
    assert rec["from_snapshot"] is not None
    assert rec["applied_parts"].tolist() == rt._parts_sent.tolist()
    exp = _expected(6, 4, 15)
    for k, v in exp.items():
        np.testing.assert_array_equal(rec["params"][k], v)
    # genesis replay of the same log agrees exactly
    gen = recover_to_vc(_x0(), wal_dir)
    for k in exp:
        np.testing.assert_array_equal(rec["params"][k], gen["params"][k])


def test_point_in_time_restore(tmp_path):
    """``upto_vc`` excludes parts timestamped past the target: the result
    is exactly the additive state of the first ``c+1`` periods."""
    _, wal_dir = _run(tmp_path, seed=7, n_clocks=12)
    for c in (3, 7):
        rec = recover_to_vc(_x0(), wal_dir, upto_vc=[c, c])
        assert rec["clock_vc"].tolist() == [c, c]
        assert rec["clock"] == c + 1
        exp = _expected(7, 4, 12, upto_ts=c)
        for k, v in exp.items():
            np.testing.assert_array_equal(rec["params"][k], v)


def test_point_in_time_skips_uncovered_snapshot(tmp_path):
    """A snapshot that already folds in updates past ``upto_vc`` cannot be
    un-applied; the picker must fall back to an older snapshot or genesis
    and still land bitwise on the point-in-time state."""
    _, wal_dir = _run(tmp_path, seed=8, n_clocks=16, snapshot_every=4,
                      snapshot_dir=str(tmp_path / "snaps"))
    rec = recover_to_vc(_x0(), wal_dir, snapshot_dir=str(tmp_path / "snaps"),
                        upto_vc=[2, 2])
    assert rec["from_snapshot"] is None       # every snapshot is too new
    exp = _expected(8, 4, 16, upto_ts=2)
    for k, v in exp.items():
        np.testing.assert_array_equal(rec["params"][k], v)


def test_f32_table_recovery_matches_live_master(tmp_path):
    """A float32 table accumulates in float32 live and on replay: with
    non-integer deltas (so every add rounds) genesis and snapshot-positioned
    recovery both reproduce the live master bitwise, in float32, and the
    log carries the parts as raw f32 frames."""
    x0 = {"a": (np.arange(32, dtype=np.float32).reshape(8, 4) / 3),
          "b": np.full(5, 0.1, dtype=np.float32)}

    def fn(w, clock, view, rng):
        return {"a": rng.normal(0.0, 0.7, size=(8, 4)),
                "b": rng.normal(0.0, 0.7, size=5)}

    sdir = str(tmp_path / "snaps")
    wal_dir = str(tmp_path / "wal")
    rt = PSRuntime(RuntimeConfig(4, policies.ssp(3), x0, n_shards=2,
                                 threads_per_process=2, seed=21,
                                 wal_dir=wal_dir, snapshot_every=4,
                                 snapshot_dir=sdir))
    st = rt.run(fn, n_clocks=14)
    assert st.violations == []
    live = {k: rt.master_value(k) for k in x0}
    assert all(v.dtype == np.float32 for v in live.values())
    gen = recover_to_vc(x0, wal_dir)
    snap = recover_to_vc(x0, wal_dir, snapshot_dir=sdir)
    assert snap["from_snapshot"] is not None
    for rec in (gen, snap):
        assert rec["applied_parts"].tolist() == rt._parts_sent.tolist()
        for k, v in live.items():
            assert rec["params"][k].dtype == np.float32
            np.testing.assert_array_equal(rec["params"][k], v)
    # the same deltas summed in float64 land elsewhere: the check has teeth
    wide = recover_to_vc({k: v.astype(np.float64) for k, v in x0.items()},
                         wal_dir)
    assert not np.array_equal(wide["params"]["a"], live["a"])
    codec = RowCodec(list(x0))
    (_, path), *_ = wal_segments(wal_dir)[0]
    records, _ = read_segment(path, codec)
    parts = [m for kind, run in records if kind == "parts" for m in run]
    assert parts and all(m.delta.dtype == np.float32 for m in parts)


def test_double_replay_is_idempotent(tmp_path):
    """At-least-once replay: feeding the same log content twice (a segment
    duplicated under another generation name) changes nothing — the vc
    stamps advance the dedup frontier past the first copy's parts, so the
    second copy is dropped uid-for-uid."""
    rt, wal_dir = _run(tmp_path, seed=9, n_clocks=10)
    clean = recover_to_vc(_x0(), wal_dir)
    for name in list(os.listdir(wal_dir)):
        base, ext = os.path.splitext(name)
        assert base.endswith("_g0000")
        dup = base[:-6] + "_g9999" + ext      # same start_part, later gen
        with open(os.path.join(wal_dir, name), "rb") as src, \
                open(os.path.join(wal_dir, dup), "wb") as dst:
            dst.write(src.read())
    rec = recover_to_vc(_x0(), wal_dir)
    assert rec["n_deduped"] > 0
    assert rec["applied_parts"].tolist() == rt._parts_sent.tolist()
    for k in clean["params"]:
        np.testing.assert_array_equal(rec["params"][k], clean["params"][k])


def test_tampered_vc_stamp_refused(tmp_path):
    """An out-of-range vc stamp in the log (bit rot / tampering) is refused
    loudly via snapshot.validate_vcs, not silently replayed."""
    w = WalWriter(str(tmp_path / "wal"), sid=0, codec=_codec(), n_proc=2)
    w.log_parts([_msg(1, 0, 0)])
    w.commit(np.array([1 << 50, 0]))          # beyond the 2^48 stamp range
    w.seal()
    with pytest.raises(ValueError, match="out-of-range"):
        recover_to_vc(_x0(), str(tmp_path / "wal"), n_proc=2)
    w2 = WalWriter(str(tmp_path / "wal2"), sid=0, codec=_codec(), n_proc=2)
    w2.log_parts([_msg(1, 0, 0)])
    w2.commit(np.array([0, 0, 0]))            # wrong width: malformed
    w2.seal()
    with pytest.raises(ValueError, match="malformed"):
        recover_to_vc(_x0(), str(tmp_path / "wal2"), n_proc=2)


def test_recovery_after_torn_tail(tmp_path):
    """Chop bytes off the live tail segment (kill mid-write): recovery
    still works, yielding a consistent prefix state (audit counters simply
    reflect the surviving parts)."""
    rt, wal_dir = _run(tmp_path, seed=11, n_clocks=10)
    full = recover_to_vc(_x0(), wal_dir)
    sid0 = wal_segments(wal_dir)[0]
    start, path = sid0[-1]
    size = os.path.getsize(path)
    with open(path, "rb+") as f:
        f.truncate(size - 7)                  # mid-record, mid-payload
    rec = recover_to_vc(_x0(), wal_dir)
    assert (rec["applied_parts"] <= full["applied_parts"]).all()
    assert rec["n_deduped"] == 0


# ---------------------------------------------------------------------------
# retention + snapshot wal-marks plumbing
# ---------------------------------------------------------------------------


def test_retention_prunes_and_restores_from_newest_pair(tmp_path):
    """``snapshot_keep_last=k`` prunes old periodic snapshots and the WAL
    segments they fully cover; restore from the newest retained
    snapshot+log pair is still exact."""
    sdir = str(tmp_path / "snaps")
    rt, wal_dir = _run(tmp_path, seed=12, n_clocks=18, snapshot_every=3,
                       snapshot_dir=sdir, snapshot_keep_last=2,
                       wal_segment_bytes=2048)
    snaps = sorted(os.listdir(sdir))
    assert len(snaps) == 2                    # pruned beyond keep_last
    assert len(rt.snapshots) == 2
    rec = recover_to_vc(_x0(), wal_dir, snapshot_dir=sdir)
    assert rec["from_snapshot"] is not None
    assert rec["applied_parts"].tolist() == rt._parts_sent.tolist()
    exp = _expected(12, 4, 18)
    for k, v in exp.items():
        np.testing.assert_array_equal(rec["params"][k], v)


def test_snapshot_wal_marks_roundtrip(tmp_path):
    rt, wal_dir = _run(tmp_path, seed=13, n_clocks=8)
    from repro.runtime.snapshot import take_snapshot
    snap = take_snapshot(rt)
    assert "wal" in snap
    p = str(tmp_path / "s.npz")
    save_snapshot(p, snap)
    back = load_snapshot(p)
    assert back["wal"]["slots"] == snap["wal"]["slots"]
    for f in ("parts", "applied", "max_ts"):
        np.testing.assert_array_equal(back["wal"][f], snap["wal"][f])
    # a snapshot taken at quiesce covers the whole log: replay adds nothing
    rec = recover_to_vc(_x0(), wal_dir, snapshot=back)
    assert rec["n_replayed"] == 0
    assert rec["applied_parts"].tolist() == rt._parts_sent.tolist()


# ---------------------------------------------------------------------------
# config validation + metrics surface
# ---------------------------------------------------------------------------


def test_config_validations(tmp_path):
    ok = dict(n_workers=2, policy=policies.ssp(1), init_params=_x0())
    with pytest.raises(ValueError, match="snapshot_dir"):
        RuntimeConfig(**ok, snapshot_every=5)
    with pytest.raises(ValueError, match="wal_dir"):
        RuntimeConfig(**ok, wal_fsync="boundary")
    with pytest.raises(ValueError, match="wal_fsync"):
        RuntimeConfig(**ok, wal_dir=str(tmp_path), wal_fsync="always")
    with pytest.raises(ValueError, match="wal_segment_bytes"):
        RuntimeConfig(**ok, wal_dir=str(tmp_path), wal_segment_bytes=0)
    with pytest.raises(ValueError, match="snapshot_keep_last"):
        RuntimeConfig(**ok, snapshot_every=5, snapshot_dir=str(tmp_path),
                      snapshot_keep_last=-1)
    with pytest.raises(ValueError, match="snapshot_dir"):
        RuntimeConfig(**ok, snapshot_keep_last=2)
    # valid combinations construct
    RuntimeConfig(**ok, wal_dir=str(tmp_path), wal_fsync="boundary")
    RuntimeConfig(**ok, snapshot_every=5, snapshot_dir=str(tmp_path),
                  snapshot_keep_last=2)
    with pytest.raises(ValueError, match="fsync"):
        WalWriter(str(tmp_path), 0, _codec(), 2, fsync="weekly")


def test_metrics_report_wal_counters(tmp_path):
    rt, _ = _run(tmp_path, seed=14, n_clocks=8, wal_fsync="boundary")
    m = rt.metrics()
    active = [s for s in m.shards if s.active]
    assert sum(s.wal_parts for s in active) == int(rt._parts_sent.sum())
    for s in active:
        assert s.wal_commits > 0
        assert s.wal_bytes > 0
        assert s.wal_segments >= 1
        assert s.wal_fsync_s > 0.0            # boundary policy paid fsyncs
    rt_off = PSRuntime(RuntimeConfig(2, policies.ssp(1), _x0()))
    rt_off.run(_fn(1), n_clocks=2)
    assert all(s.wal_parts == 0 and s.wal_commits == 0
               for s in rt_off.metrics().shards)
