"""RB1 binary batch ingest (ISSUE 7): frame codec + walker edges (torn/
short frames, bad magic/CRC, version skew), native-vs-Python walker
parity fuzz, the registry slot map / dispatch table, admission control
(quota, drop-oldest backpressure), backfill horizon boundaries, the shm
ring, and the journal's raw-FRAME write-ahead records."""

import struct
import zlib

import numpy as np
import pytest

from rtap_tpu.config import cluster_preset
from rtap_tpu.ingest import (
    BinaryBatchSource,
    DispatchTable,
    FrameWalker,
    ShmRing,
    build_frame,
    decode_slot,
    encode_slot,
)
from rtap_tpu.ingest.dispatch import decode_frames_to_row
from rtap_tpu.ingest.protocol import (
    KIND_DATA,
    KIND_MAP,
    KIND_NAMES,
    MAX_GROUPS,
    MAX_SHARDS,
    MAX_SLOTS,
    data_frame,
    scan_frames_py,
)
from rtap_tpu.service.registry import StreamGroupRegistry

try:
    from rtap_tpu.native import frame_walker_scan

    _ = frame_walker_scan(b"")
    _nat_err = None
except Exception as e:  # no toolchain: the fallback story, not a failure
    frame_walker_scan = None
    _nat_err = e

needs_native = pytest.mark.skipif(
    frame_walker_scan is None, reason=f"native walker unavailable: {_nat_err}")

pytestmark = pytest.mark.quick


def _reg(n=6, group_size=4, reserve=0):
    reg = StreamGroupRegistry(cluster_preset(), group_size=group_size,
                              backend="cpu")
    for i in range(n):
        reg.add_stream(f"s{i}")
    reg.finalize(reserve=reserve)
    return reg


def _codes(reg, *ids):
    sm = reg.slot_map()
    return np.array([encode_slot(sm[i].shard, sm[i].group, sm[i].slot)
                     for i in ids], np.uint32)


# ------------------------------------------------------------- codec ----


def test_slot_codec_roundtrip_and_bounds():
    for shard, group, slot in [(0, 0, 0), (3, 77, 1023),
                               (MAX_SHARDS - 1, MAX_GROUPS - 1,
                                MAX_SLOTS - 1)]:
        sh, g, s = decode_slot(encode_slot(shard, group, slot))
        assert (int(sh), int(g), int(s)) == (shard, group, slot)
    for bad in [(-1, 0, 0), (MAX_SHARDS, 0, 0), (0, MAX_GROUPS, 0),
                (0, 0, MAX_SLOTS)]:
        with pytest.raises(ValueError):
            encode_slot(*bad)


def test_frame_roundtrip_all_kinds():
    codes = np.array([encode_slot(0, 0, i) for i in range(3)], np.uint32)
    vals = np.array([1.5, np.nan, -7.0], np.float32)
    frames = [
        data_frame(codes, vals, 1_700_000_000, deltas=[0, 1, 2],
                   tenant="acme"),
        build_frame(KIND_NAMES, b"new.a\nnew.b"),
        build_frame(KIND_MAP, b'{"s0": 0}'),
    ]
    w = FrameWalker(native=False)
    out = w.feed(b"".join(frames))
    assert [f.kind for f in out] == [KIND_DATA, KIND_NAMES, KIND_MAP]
    assert out[0].tenant == "acme" and out[0].base_ts == 1_700_000_000
    rows = out[0].rows()
    assert np.array_equal(rows["slot"], codes)
    assert np.array_equal(rows["value"], vals, equal_nan=True)
    assert list(rows["dt"]) == [0, 1, 2]
    assert bytes(out[1].payload) == b"new.a\nnew.b"
    assert out[0].raw == frames[0]  # verbatim — the journal's payload


def test_walker_torn_frames_wait_for_bytes():
    frame = data_frame(np.array([encode_slot(0, 0, 0)], np.uint32),
                       [3.0], 1000)
    w = FrameWalker(native=False)
    # drip-feed in 3-byte chunks: nothing emits until the frame completes
    got = []
    for off in range(0, len(frame), 3):
        got += w.feed(frame[off:off + 3])
    assert len(got) == 1 and got[0].rows()["value"][0] == 3.0
    assert w.bad_crc == 0 and w.garbage_bytes == 0


def test_walker_bad_magic_resyncs_and_counts():
    frame = build_frame(KIND_NAMES, b"x")
    w = FrameWalker(native=False)
    out = w.feed(b"NOISE" + frame + b"RB" + frame)  # stray partial magic
    assert len(out) == 2
    assert w.garbage_bytes >= 5


def test_walker_bad_crc_skips_frame():
    frame = bytearray(data_frame(
        np.array([encode_slot(0, 0, 0)], np.uint32), [3.0], 1000))
    frame[-1] ^= 0xFF  # flip a CRC byte
    good = build_frame(KIND_NAMES, b"ok")
    w = FrameWalker(native=False)
    out = w.feed(bytes(frame) + good)
    assert [f.kind for f in out] == [KIND_NAMES]
    assert w.bad_crc == 1


def test_walker_version_skew_skips_whole_frame():
    """Framing fields are frozen across versions: a well-framed future-
    version (or unknown-kind) frame is skipped WHOLE and counted, never
    treated as garbage (docs/INGEST.md versioning rules)."""
    def reskew(frame: bytes, byte_off: int, value: int) -> bytes:
        b = bytearray(frame[:-4])
        b[byte_off] = value
        return bytes(b) + struct.pack("<I", zlib.crc32(bytes(b[3:])))

    good = build_frame(KIND_NAMES, b"ok")
    futures = [reskew(good, 3, 9),   # version 9
               reskew(good, 4, 200)]  # unknown kind
    w = FrameWalker(native=False)
    out = w.feed(futures[0] + futures[1] + good)
    assert [f.kind for f in out] == [KIND_NAMES]
    assert w.version_skew == 2 and w.garbage_bytes == 0


def _fuzz_stream(seed: int) -> bytes:
    rng = np.random.default_rng(seed)
    parts = []
    for _ in range(60):
        r = rng.random()
        n = int(rng.integers(1, 20))
        codes = np.array([encode_slot(0, int(rng.integers(0, 4)),
                                      int(rng.integers(0, 64)))
                          for _ in range(n)], np.uint32)
        frame = data_frame(codes, rng.normal(size=n).astype(np.float32),
                           int(rng.integers(1, 2**40)),
                           deltas=rng.integers(0, 65536, n).astype(np.uint16),
                           tenant="t" * int(rng.integers(0, 6)))
        if r < 0.55:
            parts.append(frame)
        elif r < 0.7:  # flipped byte somewhere (CRC or header damage)
            b = bytearray(frame)
            b[int(rng.integers(0, len(b)))] ^= 0xFF
            parts.append(bytes(b))
        elif r < 0.8:  # version/kind skew with a VALID crc
            b = bytearray(frame[:-4])
            b[3 if r < 0.75 else 4] = int(rng.integers(5, 250))
            parts.append(bytes(b) + struct.pack(
                "<I", zlib.crc32(bytes(b[3:]))))
        elif r < 0.9:  # raw garbage (may contain magic-like bytes)
            parts.append(bytes(rng.integers(0, 256, int(rng.integers(1, 80)),
                                            dtype=np.uint8)))
        else:  # truncated frame mid-payload
            parts.append(frame[:int(rng.integers(1, len(frame)))])
    return b"".join(parts)


@needs_native
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_walker_parity_native_vs_python_fuzz(seed):
    """The C scanner and the Python fallback must agree meta-for-meta,
    byte-for-byte, counter-for-counter on adversarial streams — the
    evidence behind auto-selecting the native walker."""
    blob = _fuzz_stream(seed)
    assert scan_frames_py(blob) == frame_walker_scan(blob)
    # and incrementally, at awkward chunk sizes
    wn, wp = FrameWalker(native=True), FrameWalker(native=False)
    fn, fp = [], []
    for off in range(0, len(blob), 1237):
        chunk = blob[off:off + 1237]
        fn += wn.feed(chunk)
        fp += wp.feed(chunk)
    assert [f.raw for f in fn] == [f.raw for f in fp]
    assert (wn.bad_crc, wn.version_skew, wn.garbage_bytes) \
        == (wp.bad_crc, wp.version_skew, wp.garbage_bytes)
    assert wn.frames == len(fn) > 0


# ------------------------------------------- slot map / dispatch table ----


def test_slot_map_matches_dispatch_order():
    reg = _reg(n=6, group_size=4, reserve=4)
    sm = reg.slot_map()
    assert list(sm) == reg.dispatch_ids()
    assert all(a.shard == 0 for a in sm.values())  # single-device
    # claims land in the map at their claimed (group, slot) address
    reg.add_stream("late")
    sm2 = reg.slot_map()
    assert list(sm2) == reg.dispatch_ids() and "late" in sm2
    table = DispatchTable(sm2)
    assert table.ids == reg.dispatch_ids()
    pos = table.lookup(table.codes)
    assert np.array_equal(pos, np.arange(table.n))


def test_dispatch_lookup_rejects_bad_codes():
    reg = _reg(n=6, group_size=4)
    table = DispatchTable.from_registry(reg)
    good = table.codes[2]
    bad = np.array([
        encode_slot(0, 2, 0),    # group beyond the fleet
        encode_slot(0, 0, 100),  # slot beyond the group... (dense bound)
        encode_slot(1, 0, 2),    # wrong shard for an existing (g, s)
        good,
    ], np.uint32)
    pos = table.lookup(bad)
    assert list(pos) == [-1, -1, -1, 2]
    # pads are NOT addressable: group 1 holds 2 live + 2 pad slots
    pad_code = np.array([encode_slot(0, 1, 3)], np.uint32)
    assert table.lookup(pad_code)[0] == -1


# ------------------------------------------------- admission control ----


def test_quota_exhaustion_and_counters():
    reg = _reg(n=4, group_size=4)
    src = BinaryBatchSource(reg.slot_map(), port=None, quota_rows=3)
    codes = src._table.codes
    src.feed_frames([data_frame(codes, [1, 2, 3, 4], 2000, tenant="a"),
                     data_frame(codes[:2], [9, 9], 2000, tenant="b")])
    v, _ = src(0)
    # tenant a: first 3 of 4 rows admitted; tenant b under quota
    assert src.rows_quota_dropped == 1
    assert v[0] == 9 and v[1] == 9 and v[2] == 3 and np.isnan(v[3])
    # quota window resets per tick
    src.feed_frames([data_frame(codes[:1], [5.0], 2001, tenant="a")])
    v2, _ = src(1)
    assert v2[0] == 5.0 and src.rows_quota_dropped == 1
    # a quota-truncated tick synthesizes journal frames that replay
    # to the EMITTED vector, not the wire rows
    src.feed_frames([data_frame(codes, [1, 2, 3, 4], 2002, tenant="a")])
    v3, _ = src(2)
    row = decode_frames_to_row(src.take_tick_frames(), 4,
                               DispatchTable.from_registry(reg))
    assert np.array_equal(row, v3, equal_nan=True)


def test_stale_epoch_frames_refused_whole():
    """A membership change bumps the map epoch; frames stamped with the
    old epoch are refused whole (a re-claimed slot code must never
    route a stale producer's rows into the NEW stream's model).
    Epoch-0 (epoch-unaware) frames stay admitted."""
    reg = _reg(n=4, group_size=4, reserve=4)
    src = BinaryBatchSource(reg.slot_map(), port=None)
    codes = src._table.codes
    old_epoch = src._map_epoch
    src.feed_frames([data_frame(codes[:1], [1.0], 100, epoch=old_epoch)])
    assert src.records_parsed == 1
    reg.add_stream("newcomer")  # claims a pad slot -> membership change
    src.set_slot_map(reg.slot_map())
    assert src._map_epoch == old_epoch + 1
    src.feed_frames([data_frame(codes[:1], [2.0], 101, epoch=old_epoch)])
    assert src.records_parsed == 1 and src.rows_stale_epoch == 1
    src.feed_frames([data_frame(codes[:1], [3.0], 102)])  # epoch 0: ok
    src.feed_frames([data_frame(codes[:1], [4.0], 103,
                                epoch=src._map_epoch)])
    assert src.records_parsed == 3


def test_inf_values_survive_backfill_and_synth_replay():
    """inf is a legal f32 wire value: it must survive the backfill
    merge AND the synthesized-frame journal replay (presence is
    not-NaN, never isfinite)."""
    reg = _reg(n=4, group_size=4)
    src = BinaryBatchSource(reg.slot_map(), port=None, quota_rows=3)
    codes = src._table.codes
    src.feed_frames([data_frame(codes, [np.inf, -np.inf, 3.0, 4.0],
                                2000, tenant="a")])
    v, _ = src(0)  # quota-truncated -> impure -> synthesized journal
    assert v[0] == np.inf and v[1] == -np.inf
    row = decode_frames_to_row(src.take_tick_frames(), 4,
                               DispatchTable.from_registry(reg))
    assert np.array_equal(row, v, equal_nan=True)
    srcb = BinaryBatchSource(reg.slot_map(), port=None,
                             backfill_horizon=1)
    srcb.feed_frames([data_frame(codes[:1], [np.inf], 3000),
                      data_frame(codes[1:2], [1.0], 3002)])
    v, _ = srcb(0)
    assert v[0] == np.inf  # merged through the bucket path


def test_map_push_on_membership_change_and_poll():
    """A membership change PUSHES the fresh map (with its new epoch) to
    every connected producer; poll_map() drains it without blocking —
    no producer is left stamping a stale epoch after someone else's
    claim/release."""
    import time

    from rtap_tpu.ingest.emit import BinaryFeedConnection

    reg = _reg(n=4, group_size=4, reserve=4)
    src = BinaryBatchSource(reg.slot_map()).start()
    try:
        with BinaryFeedConnection(src.address) as conn:
            e0 = conn.epoch
            assert conn.poll_map() is False  # nothing pushed yet
            reg.add_stream("pushed.late")
            src.set_slot_map(reg.slot_map())
            deadline = time.time() + 10
            while time.time() < deadline and not conn.poll_map():
                time.sleep(0.01)
            assert conn.epoch == e0 + 1
            assert "pushed.late" in conn.code_of
    finally:
        src.close()


def test_send_binary_splits_wide_ts_spans():
    """A batch spanning more than the u16 delta range must deliver
    EXACT timestamps across several frames, never clamp hours wrong."""
    from rtap_tpu.ingest.emit import _split_by_ts_span

    batch = [{"id": "a", "value": 1.0, "ts": 1_000},
             {"id": "b", "value": 2.0},              # ts-less: rides along
             {"id": "c", "value": 3.0, "ts": 1_000 + 65535},
             {"id": "d", "value": 4.0, "ts": 1_000 + 65536},  # overflows
             {"id": "e", "value": 5.0, "ts": 500}]   # new run's own base
    runs = _split_by_ts_span(batch)
    assert [[r["id"] for r in sub] for sub, _ in runs] \
        == [["a", "b", "c"], ["d"], ["e"]]
    for sub, base in runs:
        for r in sub:
            if "ts" in r:
                assert 0 <= r["ts"] - base <= 65535


def test_backfill_horizon_boundaries():
    reg = _reg(n=4, group_size=4)
    src = BinaryBatchSource(reg.slot_map(), port=None, backfill_horizon=2)
    c = src._table.codes
    T = 5000
    src.feed_frames([data_frame(c[:1], [1.0], T)])
    v, _ = src(0)
    assert np.isnan(v).all()  # watermark T-2: bucket T not yet due
    src.feed_frames([data_frame(c[1:2], [2.0], T + 2)])  # watermark -> T
    v, ts = src(1)
    assert v[0] == 1.0 and np.isnan(v[1:]).all() and ts == T
    # a late row INSIDE the horizon lands in its own (earlier) slot
    src.feed_frames([data_frame(c[2:3], [3.0], T + 1)])
    assert src.rows_backfilled == 1 and src.rows_late_dropped == 0
    src.feed_frames([data_frame(c[3:4], [4.0], T + 3)])  # watermark -> T+1
    v, ts = src(2)
    assert v[2] == 3.0 and ts == T + 1
    # at/below the emitted floor = beyond the horizon: dropped, counted
    src.feed_frames([data_frame(c[:1], [9.0], T + 1)])
    assert src.rows_late_dropped == 1
    v, _ = src(3)
    assert np.isnan(v[0])


def test_backpressure_drop_oldest():
    reg = _reg(n=4, group_size=4)
    src = BinaryBatchSource(reg.slot_map(), port=None, backfill_horizon=1,
                            max_pending_buckets=3)
    c = src._table.codes
    for i in range(6):  # 6 distinct future buckets > the 3-bucket bound
        src.feed_frames([data_frame(c[:1], [float(i)], 7000 + 10 * i)])
    assert src.rows_backpressure_dropped >= 2
    # the freshest data survived: drain everything due
    last = None
    for tick in range(10):
        v, _ = src(tick)
        if np.isfinite(v[0]):
            last = v[0]
    assert last == 4.0  # newest emittable bucket (7050 is above watermark)


# --------------------------------------------------------------- shm ----


def test_shm_ring_roundtrip_and_wraparound():
    import os

    name = f"rtap_t_ring_{os.getpid()}"
    ring = ShmRing.create(name, 4096)
    try:
        w = ShmRing.attach(name)
        frame = build_frame(KIND_NAMES, b"n" * 100)
        walker = FrameWalker(native=False)
        got = 0
        for k in range(200):  # ~25 KiB through a 4 KiB ring: many wraps
            assert w.push(frame)
            if k % 3 == 0:
                got += len(walker.feed(ring.drain()))
        got += len(walker.feed(ring.drain()))
        assert got == 200 and walker.bad_crc == 0
        assert walker.garbage_bytes == 0
        # a frame that cannot fit is refused, counted, never torn
        assert not w.push(build_frame(KIND_NAMES, b"x" * 5000))
        assert w.push_rejected == 1
        w.close()
    finally:
        ring.close()


def test_shm_attach_rejects_non_ring():
    from multiprocessing import shared_memory

    import os

    name = f"rtap_t_bad_{os.getpid()}"
    raw = shared_memory.SharedMemory(name=name, create=True, size=1024)
    try:
        with pytest.raises(ValueError):
            ShmRing.attach(name)
    finally:
        raw.close()
        raw.unlink()


# ---------------------------------------------- journal FRAME records ----


def test_journal_frame_records_roundtrip_and_torn_tail(tmp_path):
    from rtap_tpu.resilience.journal import (
        JournaledFrames,
        TickJournal,
        count_journal_ticks,
        last_journal_tick,
    )

    reg = _reg(n=4, group_size=4)
    src = BinaryBatchSource(reg.slot_map(), port=None)
    c = src._table.codes
    j = TickJournal(tmp_path / "j")
    frames0 = [data_frame(c, [1, 2, 3, 4], 9000)]
    j.append_tick_frames(0, 9000, 4, frames0)
    j.append_tick_frames(1, 9001, 4, [])  # no-data tick: legal, all-NaN
    j.append_tick(2, 9002, np.array([5, 6, 7, 8], np.float32))  # mixed log
    j.close()
    assert count_journal_ticks(tmp_path / "j") == 3
    assert last_journal_tick(tmp_path / "j") == 2

    j2 = TickJournal(tmp_path / "j")
    assert [r[0] for r in j2.recovered_ticks] == [0, 1, 2]
    t0 = j2.recovered_ticks[0][2]
    assert isinstance(t0, JournaledFrames) and t0.width == 4
    table = DispatchTable.from_registry(reg)
    assert np.array_equal(decode_frames_to_row([t0.blob], 4, table),
                          np.array([1, 2, 3, 4], np.float32))
    t1 = j2.recovered_ticks[1][2]
    assert np.isnan(decode_frames_to_row([t1.blob], 4, table)).all()
    with pytest.raises(ValueError):
        decode_frames_to_row([t0.blob], 5, table)  # width mismatch
    j2.close()

    # torn tail on a FRAME record truncates back to the last valid one
    seg = sorted((tmp_path / "j").glob("seg-*.rjl"))[-1]
    data = seg.read_bytes()
    seg.write_bytes(data[:-7])
    j3 = TickJournal(tmp_path / "j")
    assert j3.truncations == 1
    assert [r[0] for r in j3.recovered_ticks] == [0, 1]
    j3.close()


def test_listener_close_joins_threads_deterministically():
    """ISSUE 8 satellite: repeated open/close — with live producer
    connections blocked in recv — must leave no listener or handler
    thread behind (the conftest no-leaked-thread fixture's flake mode).
    close() wakes every handler via socket shutdown and joins bounded."""
    import socket
    import threading
    import time

    for _ in range(3):
        before = {t for t in threading.enumerate() if t.is_alive()}
        reg = _reg(n=4, group_size=4)
        src = BinaryBatchSource(reg.slot_map()).start()
        conns = [socket.create_connection(src.address) for _ in range(3)]
        # wait until every handler thread is up (it sends the MAP hello)
        for c in conns:
            c.settimeout(5.0)
            assert c.recv(1 << 16)
        src.close()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            leaked = [t for t in threading.enumerate()
                      if t.is_alive() and t not in before]
            if not leaked:
                break
            time.sleep(0.02)
        assert not leaked, f"listener close leaked threads: " \
                           f"{[t.name for t in leaked]}"
        for c in conns:
            c.close()


@pytest.mark.parametrize("handler", ["prompt", "descheduled_after_hello"])
def test_announce_leader_repoints_producers(handler, monkeypatch):
    """ISSUE 8: a fenced old leader pushes a MAP naming its successor;
    connected producers pick up the __leader__ hint (and the epoch bump
    makes their next stale-coded frame go loudly deaf here). A producer
    counts as connected from its hello on: the listener registers it for
    pushes BEFORE sending the hello, so a push that follows the hello at
    once reaches it even if the handler thread is descheduled right there
    (six test workers on a busy host did that, and the push went to
    nobody — ISSUE 50)."""
    import time

    from rtap_tpu.ingest.emit import BinaryFeedConnection

    if handler == "descheduled_after_hello":
        send_map = BinaryBatchSource._send_map

        def hello_then_stall(self, sock):
            send_map(self, sock)
            time.sleep(0.5)

        monkeypatch.setattr(BinaryBatchSource, "_send_map", hello_then_stall)
    reg = _reg(n=4, group_size=4)
    src = BinaryBatchSource(reg.slot_map()).start()
    try:
        with BinaryFeedConnection(src.address) as conn:
            assert conn.leader_hint is None
            e0 = conn.epoch
            src.announce_leader("127.0.0.1:12345")
            deadline = time.time() + 10
            while time.time() < deadline and not conn.poll_map():
                time.sleep(0.01)
            assert conn.leader_hint == "127.0.0.1:12345"
            assert conn.epoch == e0 + 1
    finally:
        src.close()


@pytest.mark.parametrize("at", ["send_lock", "frame"])
@pytest.mark.parametrize("stalled", ["hello", "push"])
@pytest.mark.parametrize("push", ["announce_leader", "set_slot_map"])
def test_a_push_racing_a_hello_leaves_the_producer_on_the_newest_map(
        push, stalled, at, monkeypatch):
    """Whatever a producer receives LAST is the newest map: which blob a
    MAP frame carries and when it is sent are decided under one lock. One
    of the two — the hello of a connecting producer, or a push to the
    connected ones — is descheduled once, on its way into the send lock or
    building its frame (between reading the map and sending it, in one
    order of the locks or the other), while a push runs whole. Read under one lock and sent under another,
    the stalled one followed the newer map with its own older one and the
    producer ended on a stale epoch (ISSUE 50's review)."""
    import threading
    import time

    from rtap_tpu.ingest import server
    from rtap_tpu.ingest.emit import BinaryFeedConnection

    reg = _reg(n=4, group_size=4, reserve=4)
    src = BinaryBatchSource(reg.slot_map()).start()

    def do_push(tag):
        if push == "announce_leader":
            src.announce_leader(f"127.0.0.1:{tag}")
        else:
            reg.add_stream(f"pushed.{tag}")
            src.set_slot_map(reg.slot_map())

    build_frame = server.build_frame
    main = threading.current_thread()
    stalling = threading.Event()

    def stall(point):
        # once, at `at`, on the stalled side only: the handler thread for
        # a hello, this thread for a push — never the push that runs whole
        me = threading.current_thread()
        if point != at or stalling.is_set() or me.name == "rtap-test-push" \
                or (me is main) != (stalled == "push"):
            return
        stalling.set()
        time.sleep(0.3)

    class StallsBeforeTaking:
        def __init__(self, lock):
            self.lock = lock

        def __enter__(self):
            stall("send_lock")
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    def build_frame_after_stall(kind, blob):
        stall("frame")
        return build_frame(kind, blob)

    def arm():
        src._send_lock = StallsBeforeTaking(src._send_lock)
        monkeypatch.setattr(server, "build_frame", build_frame_after_stall)

    try:
        if stalled == "hello":
            arm()
            other = threading.Thread(
                target=lambda: (stalling.wait(10), do_push(1)),
                name="rtap-test-push")
            other.start()
            conn = BinaryFeedConnection(src.address)
            other.join(10)
        else:
            # the producer is connected: the push that stalls is overtaken
            # by a second, whole one
            conn = BinaryFeedConnection(src.address)
            arm()
            other = threading.Thread(
                target=lambda: (stalling.wait(10), do_push(2)),
                name="rtap-test-push")
            other.start()
            do_push(1)
            other.join(10)
        with conn:
            deadline = time.time() + 1.0
            while time.time() < deadline:  # drain whatever follows the hello
                conn.poll_map()
                time.sleep(0.02)
            assert conn.epoch == src._map_epoch
            if push == "announce_leader":
                assert conn.leader_hint == src._leader_addr
            else:
                assert set(conn.code_of) == set(reg.slot_map())
    finally:
        src.close()


def test_arrival_lag_pair_is_one_atomic_tuple():
    """rtap-lint race-audit fix (ISSUE 12, docs/ANALYSIS.md): the
    latency tracker probes ``last_arrival_lag_s`` from the loop thread
    WITHOUT the source lock while handler threads record arrivals. As
    two separate attributes the (wall, ts) pair could tear — a fresh
    wall clock against a stale row ts reports a lag the wire never had —
    so the pair lives in ONE tuple rebound atomically; the property
    computes from a single snapshot."""
    import threading
    import time as _time

    reg = _reg(n=4, group_size=4)
    src = BinaryBatchSource(reg.slot_map(), port=None)
    codes = src._table.codes
    assert src.last_arrival_lag_s is None  # no data yet
    now = int(_time.time())
    src.feed_frames([data_frame(codes[:1], [1.0], now - 3)])
    lag = src.last_arrival_lag_s
    assert lag is not None and 2.0 <= lag < 60.0
    # a future-stamped producer clamps at 0, never goes negative
    src.feed_frames([data_frame(codes[:1], [2.0], now + 3600)])
    assert src.last_arrival_lag_s == 0.0
    # the surface stays a coherent snapshot under concurrent feeders:
    # every observed lag must be explainable by ONE frame's pair
    # (~0 for the future-stamped feeder, ~600 for the lagged one) —
    # a torn wall/ts mix would land far outside both bands
    stop = threading.Event()
    errs = []

    def feed(offset):
        while not stop.is_set():
            src.feed_frames([data_frame(
                codes[:1], [1.0], int(_time.time()) + offset)])

    threads = [threading.Thread(target=feed, args=(off,),
                                name=f"rtap-test-feed{off}")
               for off in (-600, 3600)]
    for t in threads:
        t.start()
    try:
        for _ in range(2000):
            lag = src.last_arrival_lag_s
            ok = lag == 0.0 or 590.0 <= lag <= 610.0
            if not ok:
                errs.append(lag)
                break
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    assert not errs, f"torn arrival pair produced impossible lag: {errs}"
