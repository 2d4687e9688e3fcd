"""Wire-transport tests: framing round-trips, partial reads, FIFO seq
assertions, shared-memory ring behavior, and tcp end-to-end integration.

Property-style: message contents, frame chunking, and batch sizes are
randomized over seeded sweeps, so the codec is exercised across array
shapes/dtypes and every short-frame split point rather than a single happy
path.
"""
import os
import queue
import threading
import time

import numpy as np
import pytest

from repro.runtime import messages as M
from repro.runtime import transport as T


def _msg_equal(a, b):
    assert type(a) is type(b)
    for f in a.__dataclass_fields__:
        va, vb = getattr(a, f), getattr(b, f)
        if isinstance(va, np.ndarray):
            np.testing.assert_array_equal(va, vb, err_msg=f"{type(a)}.{f}")
            assert va.dtype == vb.dtype
        else:
            assert va == vb, f"{type(a).__name__}.{f}: {va} != {vb}"


def _sample_msgs(rng, n=20):
    """A mixed bag of every message type with randomized array payloads."""
    out = []
    for i in range(n):
        kind = i % 7
        rows = np.sort(rng.choice(64, size=rng.integers(1, 9), replace=False))
        delta = rng.normal(size=(len(rows), int(rng.integers(1, 5))))
        if kind == 0:
            out.append(M.UpdateMsg(i, int(rng.integers(4)), 0,
                                   int(rng.integers(10)), "k", rows, delta))
        elif kind == 1:
            out.append(M.DeliverMsg(i, 1, 0, 1, 3, "key/with|chars", rows,
                                    delta))
        elif kind == 2:
            out.append(M.AckMsg(i, int(rng.integers(4))))
        elif kind == 3:
            out.append(M.ClockMsg(int(rng.integers(4)), int(rng.integers(50))))
        elif kind == 4:
            out.append(M.ClockMarker(0, 1, int(rng.integers(50))))
        elif kind == 5:
            out.append(M.FullyDelivered(i, 2, "k", rows, delta, 0))
        else:
            out.append(M.ProcDoneMsg(int(rng.integers(4))))
    out.append(M.ShardFinMsg(1))
    return out


# ---------------------------------------------------------------------------
# framing round-trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_frame_roundtrip_all_types(seed):
    rng = np.random.default_rng(seed)
    msgs = _sample_msgs(rng)
    dec = T.FrameDecoder()
    got = dec.feed(T.encode_frame(msgs))
    assert len(got) == len(msgs)
    for a, b in zip(msgs, got):
        _msg_equal(a, b)
    assert dec.pending_bytes == 0


def test_frame_roundtrip_edge_arrays():
    """Empty rows, single element, large block, f32 vs f64, non-C-order."""
    big = np.random.default_rng(0).normal(size=(500, 64))
    cases = [
        M.UpdateMsg(0, 0, 0, 0, "k", np.arange(0), np.zeros((0, 3))),
        M.UpdateMsg(1, 0, 0, 0, "k", np.arange(1), np.ones((1, 1))),
        M.UpdateMsg(2, 0, 0, 0, "k", np.arange(500), big),
        M.DeliverMsg(3, 0, 0, 0, 0, "k", np.arange(4),
                     np.ones((4, 2), dtype=np.float32)),
        M.DeliverMsg(4, 0, 0, 0, 0, "k", np.arange(4),
                     np.asfortranarray(np.ones((4, 2)))),
    ]
    for msg in cases:
        got = T.FrameDecoder().feed(T.encode_frame([msg]))
        assert len(got) == 1
        _msg_equal(msg, got[0])


@pytest.mark.parametrize("seed", [0, 1])
def test_decoder_handles_arbitrary_chunking(seed):
    """Byte-by-byte and random-split feeds must yield identical messages —
    partial reads / short frames stay buffered, never error."""
    rng = np.random.default_rng(seed)
    msgs = _sample_msgs(rng, n=10)
    stream = b"".join(T.encode_frame([m]) for m in msgs)

    # byte-by-byte
    dec = T.FrameDecoder()
    got = []
    for i in range(len(stream)):
        got.extend(dec.feed(stream[i:i + 1]))
    assert len(got) == len(msgs)
    for a, b in zip(msgs, got):
        _msg_equal(a, b)

    # random chunk sizes
    dec, got, off = T.FrameDecoder(), [], 0
    while off < len(stream):
        n = int(rng.integers(1, 200))
        got.extend(dec.feed(stream[off:off + n]))
        off += n
    assert len(got) == len(msgs)
    assert dec.pending_bytes == 0


def test_short_frame_stays_buffered():
    frame = T.encode_frame([M.AckMsg(7, 1)])
    dec = T.FrameDecoder()
    assert dec.feed(frame[:-1]) == []          # one byte short: no message
    assert dec.pending_bytes == len(frame) - 1
    got = dec.feed(frame[-1:])
    assert len(got) == 1 and got[0].uid == 7


def test_truncated_payload_raises():
    frame = bytearray(T.encode_frame([M.AckMsg(7, 1)]))
    # lie about the payload length: claim 3 fewer bytes than the pickle needs
    import struct
    plen = struct.unpack_from("<I", frame, 0)[0]
    struct.pack_into("<I", frame, 0, plen - 3)
    with pytest.raises(ValueError):
        T.FrameDecoder().feed(bytes(frame[:len(frame) - 3]))


def test_eof_sentinel_closes_stream():
    dec = T.FrameDecoder()
    msgs = dec.feed(T.encode_frame([M.AckMsg(1, 0)]) + T.eof_frame())
    assert len(msgs) == 1
    assert dec.closed
    with pytest.raises(ValueError):
        dec.feed(b"\x00")


# ---------------------------------------------------------------------------
# seq stamping + FIFO assertions
# ---------------------------------------------------------------------------


def test_wirechannel_batches_and_stamps_seq():
    sink = bytearray()
    chan = T.WireChannel("c", sink.extend)
    chan.send_many([M.AckMsg(i, 0) for i in range(5)])
    chan.send(M.ClockMsg(0, 9))
    got = T.FrameDecoder().feed(bytes(sink))
    assert [m.seq for m in got] == list(range(6))


def test_wirechannel_seq_monotone_across_threads():
    """Many sender threads share one channel: stream order must carry
    contiguous seqs (stamp + write are atomic under the channel lock)."""
    sink = bytearray()
    lock = threading.Lock()

    def write(data):
        with lock:
            sink.extend(data)

    chan = T.WireChannel("c", write)

    def sender(base):
        for i in range(50):
            chan.send(M.AckMsg(base * 1000 + i, 0))

    threads = [threading.Thread(target=sender, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = T.FrameDecoder().feed(bytes(sink))
    assert [m.seq for m in got] == list(range(200))
    fifo = T.FifoAssert()
    assert all(fifo.check("c", m.seq) is None for m in got)


def test_fifo_assert_detects_gap_reorder_replay():
    fifo = T.FifoAssert()
    assert fifo.check("a", 0) is None
    assert fifo.check("a", 1) is None
    assert "seq 3 after 1" in fifo.check("a", 3)      # gap
    assert fifo.check("a", 4) is None                 # resynced after gap
    assert fifo.check("b", 0) is None                 # per-sender state
    fifo2 = T.FifoAssert()
    fifo2.check("a", 0)
    assert fifo2.check("a", 0) is not None            # replay
    fifo3 = T.FifoAssert()
    fifo3.check("a", 1)                               # starts past 0: gap
    assert fifo3.check("a", 0) is not None            # reorder


def test_frame_roundtrip_serving_and_ackbatch_msgs():
    """The serving-tier publish messages and the batched ack round-trip the
    codec, numpy buffers (uids, vcs, dense state blocks) intact."""
    rng = np.random.default_rng(7)
    rows = np.arange(0, 12, 2)
    delta = rng.normal(size=(6, 3))
    state = {"k": {"rows": rows.copy(), "values": delta.copy()},
             "k2": {"rows": np.arange(4), "values": rng.normal(size=(4, 1))}}
    vc = np.array([3, -1, 7], dtype=np.int64)
    msgs = [
        M.AckBatchMsg(np.arange(17, dtype=np.int64), 1),
        M.ReplicaDeltaMsg(0, "k", rows, delta),
        M.ReplicaVcMsg(1, vc),
        M.ReplicaStateMsg(0, state, vc),
        M.ReplicaFinMsg(1),
    ]
    got = T.FrameDecoder().feed(T.encode_frame(msgs))
    assert [type(m) for m in got] == [type(m) for m in msgs]
    np.testing.assert_array_equal(got[0].uids, msgs[0].uids)
    assert got[0].process == 1
    np.testing.assert_array_equal(got[1].delta, delta)
    np.testing.assert_array_equal(got[2].clock_vc, vc)
    for key in state:
        np.testing.assert_array_equal(got[3].state[key]["rows"],
                                      state[key]["rows"])
        np.testing.assert_array_equal(got[3].state[key]["values"],
                                      state[key]["values"])
    assert got[4].shard == 1


def test_vap_acks_coalesce_into_batched_frames():
    """Satellite of the serving PR: per-row acks coalesce into one
    AckBatchMsg per (client, shard, flush) — the ack *message* count stays
    well below the acked-update count (clock-only policies skip acks
    entirely, so the cycle only exists under a value bound)."""
    from repro.core import policies
    from repro.runtime import PSRuntime, RuntimeConfig

    x0 = {f"k{i}": np.zeros(4) for i in range(6)}

    def fn(w, clock, view, rng):
        return {k: rng.normal(size=4) for k in x0}

    rt = PSRuntime(RuntimeConfig(2, policies.vap(1e6), x0, n_shards=2,
                   threads_per_process=1, seed=0))
    st = rt.run(fn, 30, timeout=60)
    assert st.violations == []
    # every delivered part is acked exactly once...
    assert st.n_acked_updates > 0
    # ...but the acks ride far fewer messages than updates they cover
    assert st.n_ack_msgs <= st.n_acked_updates // 2, (
        st.n_ack_msgs, st.n_acked_updates)


def test_clock_only_policies_send_no_acks():
    from repro.core import policies
    from repro.runtime import PSRuntime, RuntimeConfig

    rt = PSRuntime(RuntimeConfig(2, policies.ssp(2), {"a": np.zeros((4, 2))}, n_shards=2))
    st = rt.run(lambda w, c, v, r: {"a": np.ones((4, 2))}, 10, timeout=60)
    assert st.violations == []
    assert st.n_ack_msgs == 0 and st.n_acked_updates == 0


# ---------------------------------------------------------------------------
# x86-TSO assumption of the shm rings: runtime-checked, not just documented
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("machine", ["aarch64", "ARM64"])
def test_shm_transport_refuses_weakly_ordered_isa(monkeypatch, machine):
    """The shm ring cursors assume x86 total store ordering; on arm the
    transport must refuse loudly with a pointer at tcp, not corrupt."""
    import platform

    monkeypatch.setattr(platform, "machine", lambda: machine)
    with pytest.raises(RuntimeError, match=r'transport="tcp"'):
        T.ShmTransport(1, 1)
    with pytest.raises(RuntimeError, match="total store ordering"):
        T.require_tso()


def test_serving_shm_refuses_weakly_ordered_isa(monkeypatch):
    import platform

    from repro.core import policies
    from repro.runtime import PSRuntime, RuntimeConfig
    from repro.runtime.serving import ReplicaSet

    rt = PSRuntime(RuntimeConfig(1, policies.ssp(1), {"a": np.zeros(4)}, n_shards=1))
    monkeypatch.setattr(platform, "machine", lambda: "aarch64")
    with pytest.raises(RuntimeError, match=r'transport="tcp"'):
        ReplicaSet(rt, 1, transport="shm")


def test_runtime_flags_tampered_seq():
    """End-to-end: a frame whose seqs were tampered with on the wire is
    detected by the receiving shard's FIFO assertion."""
    from repro.core import policies
    from repro.runtime import PSRuntime, RuntimeConfig

    rt = PSRuntime(RuntimeConfig(1, policies.ssp(1), {"a": np.zeros((4, 2))}, n_shards=1))
    msgs = [M.UpdateMsg(0, 0, 0, 0, "a", np.arange(1), np.ones((1, 2)))]
    msgs[0].seq = 5                                     # wire says 5, not 0
    shard = rt.shards[0]
    assert shard._handle_batch(list(msgs)) is False
    assert any("FIFO violation" in v for v in rt.stats.violations)


# ---------------------------------------------------------------------------
# shared-memory ring
# ---------------------------------------------------------------------------


def test_shm_ring_roundtrip_with_wraparound():
    ring = T.ShmRing.create(256)       # tiny: every few frames wrap
    try:
        rng = np.random.default_rng(3)
        sent = [M.AckMsg(int(i), int(rng.integers(4))) for i in range(200)]
        got = []
        dec = T.FrameDecoder()

        def consumer():
            while len(got) < len(sent):
                got.extend(dec.feed(ring.read_available()))
                time.sleep(1e-4)

        th = threading.Thread(target=consumer)
        th.start()
        chan = T.WireChannel("r", lambda d: ring.write(d, time.monotonic() + 30))
        for m in sent:
            chan.send(m)
        th.join(timeout=30)
        assert len(got) == len(sent)
        assert [m.uid for m in got] == [m.uid for m in sent]
        assert [m.seq for m in got] == list(range(len(sent)))
    finally:
        ring.close()
        ring.unlink()


def test_shm_ring_blocks_until_drained():
    ring = T.ShmRing.create(128)
    try:
        frame = T.encode_frame([M.AckMsg(0, 0)])
        n_fit = 128 // len(frame)
        for _ in range(n_fit):
            ring.write(frame)
        state = {}

        def writer():
            t0 = time.monotonic()
            ring.write(frame, deadline=time.monotonic() + 30)
            state["blocked_for"] = time.monotonic() - t0

        th = threading.Thread(target=writer)
        th.start()
        time.sleep(0.15)
        assert th.is_alive()               # full ring: writer is blocked
        ring.read_available()              # consumer drains -> space frees
        th.join(timeout=30)
        assert not th.is_alive()
        assert state["blocked_for"] >= 0.1
    finally:
        ring.close()
        ring.unlink()


def test_wirechannel_splits_batches_over_max_frame():
    """Batches above the frame cap split into several frames (a bounded
    ring cannot take arbitrarily large frames); FIFO seqs stay contiguous."""
    frames = []
    msgs = [M.UpdateMsg(i, 0, 0, 0, "k", np.arange(16),
                        np.ones((16, 16))) for i in range(32)]
    one = len(T.encode_frame([msgs[0]]))
    chan = T.WireChannel("c", frames.append, max_frame=3 * one)
    chan.send_many(msgs)
    assert len(frames) > 1                      # actually split
    assert all(len(f) <= 3 * one for f in frames)
    got = T.FrameDecoder().feed(b"".join(frames))
    assert [m.uid for m in got] == list(range(32))
    assert [m.seq for m in got] == list(range(32))


def test_proc_runtime_handles_rows_larger_than_default_ring():
    """A key bigger than the 1 MiB default ring: capacity is sized from the
    largest part, so a whole-key Inc round-trips through the shm backend."""
    from repro.core import policies
    from repro.runtime import PSRuntime, RuntimeConfig

    big = (2048, 128)                           # 2 MiB of float64 rows
    def fn(w, clock, view, rng):
        return {"w": np.ones(big)}

    rt = PSRuntime(RuntimeConfig(2, policies.ssp(1), {"w": np.zeros(big)}, n_shards=2,
                   threads_per_process=1, seed=0, transport="shm"))
    st = rt.run(fn, 3, timeout=90)
    assert st.violations == []
    assert float(rt.master_value("w").sum()) == 2 * 3 * big[0] * big[1]


def test_shm_ring_rejects_oversized_frame():
    ring = T.ShmRing.create(64)
    try:
        with pytest.raises(ValueError, match="exceeds ring capacity"):
            ring.write(b"x" * 65)
    finally:
        ring.close()
        ring.unlink()


def test_shm_ring_write_timeout():
    ring = T.ShmRing.create(32)
    try:
        ring.write(b"x" * 30)
        with pytest.raises(RuntimeError, match="timed out"):
            ring.write(b"y" * 10, deadline=time.monotonic() + 0.3)
    finally:
        ring.close()
        ring.unlink()


def test_shm_ring_survives_stale_cursor_reads():
    """Cross-process cursor reads can transiently return stale values on
    virtualized hosts (observed in the wild: a reader briefly seeing
    tail=0 after thousands of bytes).  A bogus reading must never reach
    the ring arithmetic — the old code computed a *negative* available
    count and rewound head, replaying the whole stream."""
    ring = T.ShmRing.create(64)
    try:
        ring.write(b"a" * 10)
        assert ring.read_available() == b"a" * 10
        ring.write(b"b" * 10)
        # simulate a stale tail read (behind head): must read as empty and
        # must NOT move the head cursor
        good_tail = ring._tail()
        ring._set_tail(0)
        assert ring.read_available() == b""
        assert ring._head() == 10
        # ...and a garbage tail far beyond what the ring could hold
        ring._set_tail(10 + ring.capacity + 1)
        assert ring.read_available() == b""
        ring._set_tail(good_tail)
        assert ring.read_available() == b"b" * 10        # stream intact
        # producer side: a stale head must clamp free space to "full",
        # never overstate it (that would overwrite unread bytes)
        good_head = ring._head()
        ring._set_head(ring._tail() + 1)
        assert ring.free_bytes() == 0
        assert not ring.try_write(b"x")
        ring._set_head(good_head)
        assert ring.free_bytes() == ring.capacity
    finally:
        ring.close()
        ring.unlink()


# ---------------------------------------------------------------------------
# tcp end-to-end
# ---------------------------------------------------------------------------


def test_tcp_transport_duplex_end_to_end():
    """One connection per (process, shard) pair; framed messages flow both
    directions and arrive in FIFO order with contiguous seqs."""
    tp = T.TcpTransport(n_proc=2, n_shards=2)
    tp.listen()
    client_conns = {}

    def client(pid):
        client_conns[pid] = tp.connect(pid)

    threads = [threading.Thread(target=client, args=(p,)) for p in range(2)]
    for t in threads:
        t.start()
    conns = tp.accept_all(deadline=time.monotonic() + 30)
    for t in threads:
        t.join()
    assert set(conns) == {(p, s) for p in range(2) for s in range(2)}

    try:
        # client 1 -> shard 0: a batched frame of updates
        chan = T.WireChannel("p1->s0", client_conns[1][0].write)
        rows = np.arange(3)
        chan.send_many([M.UpdateMsg(i, 2, 1, 0, "k", rows, np.ones((3, 2)) * i)
                        for i in range(10)])
        inbox = queue.Queue()
        errs = []
        T.start_reader("rx", conns[(1, 0)].read_chunk, inbox, errs.append)
        got = [inbox.get(timeout=10) for _ in range(10)]
        assert [m.uid for m in got] == list(range(10))
        assert [m.seq for m in got] == list(range(10))
        np.testing.assert_array_equal(got[3].delta, np.ones((3, 2)) * 3)

        # shard 0 -> client 1 on the same connection (duplex)
        back = T.WireChannel("s0->p1", conns[(1, 0)].write)
        back.send(M.ShardFinMsg(0))
        inbox2 = queue.Queue()
        T.start_reader("rx2", client_conns[1][0].read_chunk, inbox2,
                       errs.append)
        fin = inbox2.get(timeout=10)
        assert isinstance(fin, M.ShardFinMsg) and fin.shard == 0
        assert errs == []
    finally:
        for conn in conns.values():
            conn.close()
        for cs in client_conns.values():
            for conn in cs.values():
                conn.close()


# ---------------------------------------------------------------------------
# zero-copy raw wire (RowCodec + RingViewReader)
# ---------------------------------------------------------------------------


def _mk_zero_copy(cap=1 << 16, keys=("k", "k2")):
    ring = T.ShmRing.create(cap)
    codec = T.RowCodec(list(keys))
    bell = os.pipe()
    reader = T.RingViewReader(ring, codec, bell[0], threading.Event())
    chan = T.WireChannel("zc", T.ring_parts_writer(ring),
                         max_frame=cap // 4, codec=codec,
                         on_flush=lambda: T.ShmEdge.ring_bell(bell[1]))
    return ring, codec, reader, chan, bell


def _close_zero_copy(ring, bell):
    # decoded views must be dropped before the segment closes, else
    # SharedMemory.__del__ trips over the exported buffers at GC time
    import gc
    gc.collect()
    ring.close()
    ring.unlink()
    for fd in bell:
        try:
            os.close(fd)
        except OSError:
            pass


def _ring_mem(reader):
    return np.frombuffer(reader.ring.buf, dtype=np.uint8)


@pytest.mark.parametrize("seed", [0, 1])
def test_raw_wire_roundtrip_mixed_batch(seed):
    """Raw-eligible Update/Deliver msgs and pickle fallbacks (control msgs,
    unknown keys, f32 deltas) interleave on one stream in FIFO order."""
    rng = np.random.default_rng(seed)
    ring, codec, reader, chan, bell = _mk_zero_copy()
    try:
        msgs = []
        for i in range(24):
            rows = np.sort(rng.choice(64, size=int(rng.integers(1, 9)),
                                      replace=False)).astype(np.int64)
            delta = rng.normal(size=(len(rows), 3))
            kind = i % 4
            if kind == 0:
                msgs.append(M.UpdateMsg(i, 1, 0, int(rng.integers(9)), "k",
                                        rows, delta, epoch=2))
            elif kind == 1:
                msgs.append(M.DeliverMsg(i, 1, 0, 1, 3, "k2", rows, delta))
            elif kind == 2:
                msgs.append(M.AckMsg(i, 1))               # pickle fallback
            else:
                msgs.append(M.UpdateMsg(i, 1, 0, 0, "unknown-key", rows,
                                        delta.astype(np.float32)))
        chan.send_many(msgs)
        got = []
        while len(got) < len(msgs):
            got.extend(reader._decode_ready())
        assert [m.seq for m in got] == list(range(len(msgs)))
        for a, b in zip(msgs, got):
            _msg_equal(a, b)
        T.release_msgs(got)
        # EOF closes the zero-copy stream like the pickle one
        chan.close()
        assert reader.read_msgs() is None
        assert reader.closed
        got.clear()
    finally:
        _close_zero_copy(ring, bell)


def test_raw_wire_carries_f32_deltas():
    """A float32 table's parts travel as raw frames too: f32 and f64 deltas
    (odd widths, so later headers sit unaligned) decode in FIFO order with
    their dtype, as views into the ring."""
    rng = np.random.default_rng(4)
    ring, codec, reader, chan, bell = _mk_zero_copy()
    try:
        msgs = []
        for i in range(12):
            rows = np.arange(int(rng.integers(1, 6)), dtype=np.int64)
            dt = np.float32 if i % 3 else np.float64
            delta = rng.normal(size=(len(rows), 3)).astype(dt)
            msgs.append(M.UpdateMsg(i, 1, 0, i, "k", rows, delta))
        assert all(codec._raw_ok(m) for m in msgs)
        chan.send_many(msgs)
        got = []
        while len(got) < len(msgs):
            got.extend(reader._decode_ready())
        mem = _ring_mem(reader)
        for a, b in zip(msgs, got):
            _msg_equal(a, b)
            assert np.shares_memory(b.delta, mem)
        T.release_msgs(got)
        got.clear()
        del a, b, mem
    finally:
        _close_zero_copy(ring, bell)


def test_zero_copy_views_alias_ring_until_released():
    """Raw frames decode as views INTO the ring; the shared head cursor
    holds at the pinned frame and only advances once every message from it
    is released — that is the whole zero-copy contract."""
    ring, codec, reader, chan, bell = _mk_zero_copy()
    try:
        rows = np.arange(5, dtype=np.int64)
        chan.send_many([M.UpdateMsg(i, 0, 0, 0, "k", rows,
                                    np.full((5, 2), float(i)))
                        for i in range(3)])
        got = reader._decode_ready()
        assert len(got) == 3
        mem = _ring_mem(reader)
        for m in got:
            assert np.shares_memory(m.rows, mem)
            assert np.shares_memory(m.delta, mem)
        del m
        assert reader.pinned_frames() == 1
        assert ring._head() == 0                   # nothing released yet
        T.release_msgs(got[:2])
        assert ring._head() == 0                   # frame still partly pinned
        T.release_msg(got[2])
        assert reader.pinned_frames() == 0
        assert ring._head() == ring._tail()        # fully drained
        got.clear()
        del mem
    finally:
        _close_zero_copy(ring, bell)


def test_materialize_unpins_and_owns():
    """materialize_msg copies the arrays out of the ring (no aliasing — the
    use-after-advance guard) and drops the pin so the head can advance."""
    ring, codec, reader, chan, bell = _mk_zero_copy()
    try:
        rows = np.arange(4, dtype=np.int64)
        delta = np.ones((4, 3)) * 7.0
        chan.send(M.UpdateMsg(0, 0, 0, 0, "k", rows, delta))
        (m,) = reader._decode_ready()
        mem = _ring_mem(reader)
        assert np.shares_memory(m.delta, mem)
        T.materialize_msg(m)
        assert not np.shares_memory(m.rows, mem)
        assert not np.shares_memory(m.delta, mem)
        assert m._frame is None
        np.testing.assert_array_equal(m.delta, delta)
        assert reader.pinned_frames() == 0
        assert ring._head() == ring._tail()
        # the owned copy survives the producer overwriting the ring bytes
        chan.send(M.UpdateMsg(1, 0, 0, 0, "k", rows, delta * -1))
        (m2,) = reader._decode_ready()
        np.testing.assert_array_equal(m.delta, delta)
        T.release_msg(m2)
        del m2, mem
    finally:
        _close_zero_copy(ring, bell)


def test_zero_copy_frame_straddling_wraparound_copies_out():
    """A raw frame that straddles the ring wrap point cannot be viewed
    contiguously: it must decode from an owned copy (no pin, no aliasing)
    and the stream must stay intact across the wrap."""
    rows = np.arange(3, dtype=np.int64)

    def msg(i):
        return M.UpdateMsg(i, 0, 0, 0, "k", rows, np.full((3, 1), float(i)))

    codec = T.RowCodec(["k"])
    one = sum(len(p) if isinstance(p, bytes) else p.nbytes
              for p in codec._pack_raw([msg(0)])) + 4
    cap = int(one * 2.5)                # third frame is forced to straddle
    ring, codec, reader, chan, bell = _mk_zero_copy(cap=cap, keys=("k",))
    try:
        mem = _ring_mem(reader)
        straddled = 0
        for i in range(8):
            chan.send(msg(i))
            (m,) = reader._decode_ready()
            assert m.uid == i
            np.testing.assert_array_equal(m.delta, np.full((3, 1), float(i)))
            body = (m.seq * one + 4) % cap if False else None  # doc only
            if np.shares_memory(m.delta, mem):
                T.release_msg(m)
            else:
                straddled += 1
                assert getattr(m, "_frame", None) is None   # owned, unpinned
            assert reader.pinned_frames() == 0
            assert ring._head() == ring._tail()
            del m
        assert straddled > 0            # the wrap path actually ran
        del mem
    finally:
        _close_zero_copy(ring, bell)


def test_doorbell_batched_per_flush_but_rings_every_frame_on_split():
    """The common single-frame flush gets exactly one doorbell wake; a
    batch the codec splits rings once per frame — every published frame
    must be belled before the next write could block on ring space, or a
    parked reader never drains it and the producer spins forever."""
    frames, bells = [], []
    codec = T.RowCodec(["k"])
    rows = np.arange(8, dtype=np.int64)
    msgs = [M.UpdateMsg(i, 0, 0, 0, "k", rows, np.ones((8, 8)))
            for i in range(16)]
    one = codec.raw_size(msgs[0])
    chan = T.WireChannel("c", frames.append, max_frame=2 * one + 64,
                         codec=codec, on_flush=lambda: bells.append(1))
    chan.send_many([msgs[0]])
    assert len(frames) == 1 and len(bells) == 1   # single frame: one bell
    chan.send_many(msgs)
    assert len(frames) > 5              # split into several raw frames...
    assert len(bells) == len(frames)    # ...each belled (none strandable)
    n = len(frames)
    chan.close()                        # EOF frame + its wake so the reader
    assert len(frames) == n + 1         # can see the stream end and exit
    assert len(bells) == len(frames)


def test_multi_frame_batch_larger_than_ring_does_not_deadlock():
    """Deadlock regression: a send_many batch whose frames total more than
    the ring's free capacity must complete against a reader parked on the
    doorbell.  Before the per-frame bell, the producer published early
    frames un-belled and then spun for space while the reader slept in
    os.read — head never advanced and the run hung until the deadline."""
    cap = 1 << 13                          # 8 KiB ring
    ring = T.ShmRing.create(cap)
    codec = T.RowCodec(["k"])
    bell = os.pipe()
    stop = threading.Event()
    reader = T.RingViewReader(ring, codec, bell[0], stop)
    deadline = time.monotonic() + 20       # regression fails loudly, not ∞
    chan = T.WireChannel("zc", T.ring_parts_writer(ring, deadline),
                         max_frame=cap // 4, codec=codec,
                         on_flush=lambda: T.ShmEdge.ring_bell(bell[1]))
    inbox: "queue.Queue" = queue.Queue()
    errs: list = []
    t = T.start_view_reader("rx", reader, inbox, errs.append)
    try:
        rows = np.arange(8, dtype=np.int64)
        msgs = [M.UpdateMsg(i, 0, 0, 0, "k", rows, np.ones((8, 8)) * i)
                for i in range(24)]        # ~15 KiB of raw frames > cap
        sender = threading.Thread(target=chan.send_many, args=(msgs,))
        sender.start()
        got = []
        while len(got) < len(msgs):
            m = inbox.get(timeout=15)      # hangs here before the fix
            T.materialize_msg(m)
            got.append(m)
        sender.join(timeout=15)
        assert not sender.is_alive()
        assert errs == []
        assert [m.seq for m in got] == list(range(len(msgs)))
        for i, m in enumerate(got):
            assert np.array_equal(m.delta, np.ones((8, 8)) * i)
        chan.close()
        assert t.join(timeout=10) is None and not t.is_alive()
        got.clear()
    finally:
        stop.set()
        T.ShmEdge.ring_bell(bell[1])
        _close_zero_copy(ring, bell)


def test_use_after_advance_guard_through_shard_apply():
    """Drive view-backed messages through a real ServerShard batch: after
    _handle_batch returns, every frame must be released (head advanced) and
    nothing the shard retained may alias ring memory."""
    from repro.core import policies
    from repro.runtime import PSRuntime, RuntimeConfig

    x0 = {"k": np.zeros((8, 2)), "k2": np.zeros((8, 2))}
    rt = PSRuntime(RuntimeConfig(2, policies.vap(1e6), x0, n_shards=1))
    shard = rt.shards[0]
    ring, codec, reader, chan, bell = _mk_zero_copy()
    try:
        rows = np.arange(4, dtype=np.int64)
        batch = [M.UpdateMsg(i, 0, 0, 0, "k", rows, np.ones((4, 2)))
                 for i in range(4)]
        batch += [M.UpdateMsg(4 + i, 0, 0, 0, "k2", rows, np.ones((4, 2)))
                  for i in range(2)]
        chan.send_many(batch)
        got = []
        while len(got) < len(batch):
            got.extend(reader._decode_ready())
        assert all(getattr(m, "_frame", None) is not None for m in got)
        assert shard._handle_batch(got) is False     # no shutdown sentinel
        assert rt.stats.violations == []
        # every pin dropped: the read cursor is free to advance
        assert reader.pinned_frames() == 0
        assert ring._head() == ring._tail()
        # ...and whatever the shard retained past the batch (pending VAP
        # deliveries, queued updates, held msgs) owns its arrays
        mem = _ring_mem(reader)
        retained = [m for m, _ in shard.pending.values()]
        retained += [m for q in shard.queued.values() for m in q]
        retained += list(shard._held)
        assert retained, "expected the VAP path to retain deliveries"
        for m in retained:
            assert not np.shares_memory(m.rows, mem)
            assert not np.shares_memory(m.delta, mem)
            assert getattr(m, "_frame", None) is None
        got.clear()
        del mem
    finally:
        _close_zero_copy(ring, bell)


def test_tcpconn_probes_ioctl_once_and_tracks_live_sndbuf():
    """room() must not re-import fcntl/termios per call (the probe happens
    once at connection setup), but it must read the LIVE SO_SNDBUF each
    time — Linux autotunes the send buffer upward when it was never set
    explicitly, and a stale cached size would under-report room() and
    refuse sends that fit."""
    import builtins
    import socket

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    cli = socket.create_connection(srv.getsockname())
    peer, _ = srv.accept()
    try:
        conn = T.TcpConn(cli)
        real_import = builtins.__import__

        def poisoned(name, *a, **kw):
            if name in ("fcntl", "termios"):
                raise AssertionError(f"room() re-imported {name}")
            return real_import(name, *a, **kw)

        builtins.__import__ = poisoned
        try:
            r1 = conn.room()
            r2 = conn.room()
        finally:
            builtins.__import__ = real_import
        assert r1 >= 0 and r2 >= 0
        if conn._ioctl is not None:
            sndbuf = cli.getsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF)
            assert r1 == sndbuf            # nothing queued yet
            # growing the kernel buffer must be visible to the next room()
            # call: room() tracks the live getsockopt reading, not a
            # setup-time cache
            cli.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 2 * sndbuf)
            new = cli.getsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF)
            assert conn.room() == new
        # degraded fallback: no ioctl -> "unknown" room + select probe
        conn._ioctl = None
        assert conn.room() == 1 << 62
        assert conn.try_write(b"ping")
        assert peer.recv(4) == b"ping"
    finally:
        for s in (cli, peer, srv):
            s.close()
