"""Unit coverage for hot-standby replication (ISSUE 8).

The failover soak (tests/integration/test_failover.py) owns the
end-to-end kill-9 verdict; these tests pin the components: the lease's
acquire/fence/heartbeat semantics, the RJ wire walker's torn/corrupt
tolerance, the sender's bounded drop-oldest buffer and compaction
clamp (the PR 5 pause rule applied to replication), the chaos wire
fault kinds (digest-stable for existing seeds, fire-once on retry),
the in-process leader->standby apply path (bit-identical state, alert
buffering pruned by cursors), and the compaction-gap ->
full-checkpoint-fetch fallback.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from rtap_tpu.config import cluster_preset
from rtap_tpu.resilience.chaos import (
    FAULT_KINDS,
    GENERATED_KINDS,
    ChaosEngine,
    ChaosSpec,
    Fault,
)
from rtap_tpu.resilience.journal import (
    TickJournal,
    first_journal_tick,
    iter_raw_records,
)
from rtap_tpu.resilience.replicate import (
    WIRE_ACK,
    WIRE_HELLO,
    WIRE_SNAP,
    Lease,
    ReplicationSender,
    StandbyFollower,
    WireWalker,
    pack_wire,
)
from rtap_tpu.service.loop import live_loop
from rtap_tpu.service.registry import StreamGroupRegistry

pytestmark = pytest.mark.quick


def _reg(n=4, gs=2, threshold=-1e9):
    reg = StreamGroupRegistry(cluster_preset(), group_size=gs,
                              backend="cpu", threshold=threshold,
                              debounce=1)
    for i in range(n):
        reg.add_stream(f"s{i}")
    reg.finalize()
    return reg


def _row(seed, k, n):
    rng = np.random.Generator(np.random.Philox(key=(seed, k)))
    return (30 + 5 * rng.random(n)).astype(np.float32), 1_700_000_000 + k


def _state_fingerprint(grp):
    out = {"ticks": grp.ticks}
    for g, st in enumerate(grp._states):
        for k, v in st.items():
            out[f"s{g}/{k}"] = np.asarray(v)
    for k, v in grp.likelihood.state_dict().items():
        out[f"lik/{k}"] = np.asarray(v)
    return out


def _assert_groups_equal(a, b):
    for ga, gb in zip(a.groups, b.groups):
        fa, fb = _state_fingerprint(ga), _state_fingerprint(gb)
        assert sorted(fa) == sorted(fb)
        for k in fa:
            np.testing.assert_array_equal(np.asarray(fa[k]),
                                          np.asarray(fb[k]), err_msg=k)


# ---------------------------------------------------------------- lease
def test_lease_acquire_refresh_and_fence(tmp_path):
    path = tmp_path / "lease"
    a = Lease(path, "A", timeout_s=0.4)
    assert a.try_acquire()
    assert a.epoch == 1
    assert a.refresh()
    # a fresh foreign lease refuses a second owner
    b = Lease(path, "B", timeout_s=0.4)
    assert not b.try_acquire()
    assert not b.is_stale()
    # staleness admits the takeover and BUMPS the epoch (the fence)
    time.sleep(0.5)
    assert b.is_stale()
    assert b.try_acquire()
    assert b.epoch == 2
    # the old holder is fenced — sticky, on both probes
    assert not a.refresh()
    assert a.fenced
    assert not a.still_mine()
    # and a fenced lease can never re-acquire
    assert not a.try_acquire()
    # the file records the winner
    assert b.holder() == "B"
    assert json.loads(path.read_text())["epoch"] == 2


def test_lease_heartbeat_keeps_it_fresh_through_a_stall(tmp_path):
    path = tmp_path / "lease"
    a = Lease(path, "A", timeout_s=0.4)
    assert a.try_acquire()
    a.start_heartbeat()
    try:
        b = Lease(path, "B", timeout_s=0.4)
        # the OWNER thread does nothing for 3x the timeout — liveness
        # must come from the heartbeat thread, not the tick loop
        deadline = time.monotonic() + 1.2
        while time.monotonic() < deadline:
            assert not b.is_stale()
            time.sleep(0.1)
        assert not b.try_acquire()
    finally:
        a.stop_heartbeat()


def test_woken_zombie_heartbeat_never_clobbers_the_new_leader(tmp_path):
    path = tmp_path / "lease"
    a = Lease(path, "A", timeout_s=0.3)
    assert a.try_acquire()
    b = Lease(path, "B", timeout_s=0.3)
    time.sleep(0.4)
    assert b.try_acquire()  # epoch 2
    # A "wakes up": its next refresh must fence, not overwrite
    assert not a.refresh()
    cur = json.loads(path.read_text())
    assert cur["owner"] == "B" and cur["epoch"] == 2


def test_lease_acquire_over_unreadable_file_still_bumps_past_leader(
        tmp_path):
    """An acquire whose read finds the file missing/unreadable must
    bump past the highest epoch EVER OBSERVED, never restart at 1 —
    restarting would invert the fence (the old leader at epoch N>1
    keeps serving, the promoted standby fences itself)."""
    path = tmp_path / "lease"
    # a leader several failovers in: epoch 7, stalled past the timeout
    path.write_text(json.dumps(
        {"epoch": 7, "owner": "A", "ts": time.time() - 9.0}))
    a = Lease(path, "A", timeout_s=0.3)
    a.epoch = 7
    b = Lease(path, "B", timeout_s=0.3)
    assert b.is_stale()  # B OBSERVES epoch 7 via this read
    path.unlink()  # transient shared-fs fault at the worst moment
    assert b.try_acquire()
    assert b.epoch == 8  # bumped past the observed epoch, not reset to 1
    assert not a.refresh()
    assert a.fenced


def test_lease_set_meta_is_safe_under_a_live_heartbeat(tmp_path):
    """set_meta rebinds (never mutates) the meta dict: an in-place
    insert racing the heartbeat thread's ``{**self.meta}`` unpack would
    raise and silently kill the thread."""
    path = tmp_path / "lease"
    a = Lease(path, "A", timeout_s=0.4)
    assert a.try_acquire()
    a.start_heartbeat()
    try:
        for i in range(200):
            a.set_meta(**{f"k{i % 7}": i, "ingest": f"h:{i}"})
        time.sleep(0.3)  # a few heartbeat periods with churned meta
        assert a._hb_thread.is_alive()
        assert json.loads(path.read_text())["ingest"] == "h:199"
    finally:
        a.stop_heartbeat()


# ----------------------------------------------------------- wire layer
def test_wire_walker_roundtrip_torn_and_corrupt():
    w = WireWalker()
    recs = [pack_wire(WIRE_HELLO, np.int64(7).tobytes()),
            pack_wire(WIRE_ACK, np.int64(9).tobytes()),
            pack_wire(WIRE_SNAP, np.int64(3).tobytes())]
    blob = b"".join(recs)
    # torn delivery: byte-at-a-time still yields every record in order
    out = []
    for i in range(len(blob)):
        out += w.feed(blob[i:i + 1])
    assert [t for t, _p in out] == [WIRE_HELLO, WIRE_ACK, WIRE_SNAP]
    assert w.garbage_bytes == 0 and w.bad_crc == 0
    # a corrupt record is skipped by CRC; the NEXT record still parses
    bad = bytearray(recs[0])
    bad[len(bad) // 2] ^= 0xFF
    out = w.feed(bytes(bad) + recs[1])
    assert [t for t, _p in out] == [WIRE_ACK]
    assert w.bad_crc + (1 if w.garbage_bytes else 0) >= 1
    # pure garbage resyncs without emitting records
    out = w.feed(b"x" * 64 + recs[2])
    assert [t for t, _p in out] == [WIRE_SNAP]
    assert w.garbage_bytes >= 64


def test_journal_tee_ships_exact_record_bytes(tmp_path):
    shipped = []
    j = TickJournal(tmp_path / "j")
    j.tee = lambda typ, tick, rec: shipped.append((typ, tick, rec))
    j.append_tick(0, 100, np.arange(3, dtype=np.float32))
    j.append_cursor(0, 55)
    j.append_tick_frames(1, 101, 3, [b"rawframe"])
    j.close()
    assert [(t, k) for t, k, _r in shipped] == [(1, 0), (2, 0), (3, 1)]
    # the teed bytes ARE the on-disk bytes (the mirror is byte-exact)
    disk = [rec for _t, _k, rec in iter_raw_records(tmp_path / "j", 0)]
    assert disk == [r for _t, _k, r in shipped]
    # and the wire walker accepts them as-is
    w = WireWalker()
    out = w.feed(b"".join(r for _t, _k, r in shipped))
    assert [t for t, _p in out] == [1, 2, 3]


# ------------------------------------------------- sender buffer + clamp
def test_sender_buffer_is_bounded_drop_oldest(tmp_path):
    j = TickJournal(tmp_path / "j")
    # nothing listening on a closed port: the sender can never drain
    s = ReplicationSender(("127.0.0.1", 1), j, max_buffer=16)
    for k in range(100):
        s.tee(1, k, b"x" * 20)
    assert len(s._q) == 16
    assert s.dropped_records == 84
    # drop-oldest: the newest records survive
    assert [t for _typ, t, _r in s._q] == list(range(84, 100))
    j.close()


def test_compaction_clamped_to_standby_ack(tmp_path):
    # tiny segments force rotation so compact() has segments to drop
    j = TickJournal(tmp_path / "j", segment_bytes=1024)
    row = np.arange(64, dtype=np.float32)
    for k in range(40):
        j.append_tick(k, 100 + k, row)
    s = ReplicationSender(("127.0.0.1", 1), j, max_buffer=64)
    j.compact_floor = s.compact_floor
    # CONNECTED and lagging: the pause rule — nothing the standby has
    # not acked past may be dropped, whatever the checkpoints say
    s.connected = True
    s.acked_tick = 5
    j.compact(40)
    assert first_journal_tick(tmp_path / "j") <= 6
    # the standby catches up: compaction may proceed
    s.acked_tick = 39
    j.compact(30)
    assert first_journal_tick(tmp_path / "j") >= 7
    # DISCONNECTED: the clamp lifts entirely (bounded disk growth; a
    # reconnect past the gap takes the checkpoint-fetch fallback)
    s.connected = False
    j.compact(40)
    assert j.stats()["segments"] <= 2
    j.close()


# -------------------------------------------------- chaos wire faults
def test_generated_kinds_exclude_wire_and_proc_exit_kinds():
    for kind in ("proc_exit", "conn_drop", "stall_socket",
                 "corrupt_bytes"):
        assert kind in FAULT_KINDS
        assert kind not in GENERATED_KINDS
    # the pre-ISSUE-8 digest pin: adding kinds must not shift existing
    # seeds' generated schedules
    assert ChaosSpec.generate(seed=3, n_ticks=40,
                              n_groups=2).digest() == "b804a3aefde807d4"


def test_on_wire_faults_fire_once_per_scheduled_fault():
    spec = ChaosSpec(faults=[Fault(kind="conn_drop", tick=3),
                             Fault(kind="corrupt_bytes", tick=5),
                             Fault(kind="stall_socket", tick=7,
                                   seconds=0.01)])
    eng = ChaosEngine(spec)
    data = b"A" * 32
    assert eng.on_wire(2, data) == data
    with pytest.raises(ConnectionResetError):
        eng.on_wire(3, data)
    # the retry of the SAME record passes: a fault, not an outage
    assert eng.on_wire(3, data) == data
    out = eng.on_wire(5, data)
    assert out != data and len(out) == len(data)
    assert eng.on_wire(5, data) == data  # fire-once for corruption too
    t0 = time.perf_counter()
    assert eng.on_wire(7, data) == data
    assert time.perf_counter() - t0 >= 0.01
    assert sorted(e["kind"] for e in eng.injected) == [
        "conn_drop", "corrupt_bytes", "stall_socket"]


# ------------------------------------- follower: apply + splice + snap
def _run_pair(tmp_path, n_ticks, leader_kw=None, standby_journal=None,
              ck=None):
    """Drive a leader live_loop shipping to an in-process follower;
    returns (leader_reg, standby_reg, follower, leader stats)."""
    leader, standby = _reg(), _reg()
    ck = ck or str(tmp_path / "ck")
    lease_path = tmp_path / "lease"
    llease = Lease(lease_path, "L", timeout_s=30.0)
    assert llease.try_acquire()
    slease = Lease(lease_path, "S", timeout_s=1e9)
    stop = threading.Event()
    sj = standby_journal or TickJournal(tmp_path / "sj")
    follower = StandbyFollower(standby, sj, lease=slease, port=0,
                               alert_path=str(tmp_path / "alerts.jsonl"),
                               checkpoint_dir=ck, stop_event=stop)
    t = threading.Thread(target=follower.run, daemon=True)
    t.start()
    deadline = time.monotonic() + 20
    while follower.address is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert follower.address is not None
    lj = TickJournal(tmp_path / "lj")
    sender = ReplicationSender(follower.address, lj,
                               checkpoint_dir=ck).start()
    lj.tee, lj.compact_floor = sender.tee, sender.compact_floor
    stats = live_loop(
        lambda k: _row(7, k, 4), leader, n_ticks=n_ticks, cadence_s=0.0,
        alert_path=str(tmp_path / "alerts.jsonl"), checkpoint_dir=ck,
        checkpoint_every=5, journal=lj, lease=llease,
        **(leader_kw or {}))
    deadline = time.monotonic() + 30
    while follower.expected < n_ticks and time.monotonic() < deadline:
        time.sleep(0.01)
    lj.close()
    sender.close()
    stop.set()
    t.join(timeout=20)
    sj.close()
    return leader, standby, follower, stats


def test_follower_applies_stream_bit_identically(tmp_path):
    leader, standby, follower, _stats = _run_pair(tmp_path, 12)
    assert follower.applied == 12
    _assert_groups_equal(leader, standby)
    # cursors pruned the buffer: everything shipped was delivered
    assert follower.stats()["buffered_alerts"] == 0
    assert follower.last_cursor is not None
    # the mirror is byte-identical to the leader's journal records
    lrecs = [r for _t, _k, r in
             iter_raw_records(tmp_path / "lj", 0)]
    srecs = [r for _t, _k, r in
             iter_raw_records(tmp_path / "sj", 0)]
    assert lrecs == srecs


def test_snapshot_fallback_after_compaction_gap(tmp_path):
    # the reconnect-after-gap drill: the standby adopts the shared
    # checkpoints at tick 8, then the leader serves ON ALONE —
    # checkpointing + compacting until the journal no longer holds
    # tick 8 (no standby connected = no clamp). When the sender finally
    # connects, the standby's HELLO(8) cannot be served from disk: the
    # leader sends SNAP, the standby re-adopts the (newer) shared
    # checkpoints, re-HELLOs from there, and catches up — final state
    # bit-identical.
    leader = _reg()
    ck = str(tmp_path / "ck")
    lj = TickJournal(tmp_path / "lj", segment_bytes=1024)
    live_loop(lambda k: _row(7, k, 4), leader, n_ticks=8, cadence_s=0.0,
              alert_path=str(tmp_path / "alerts.jsonl"),
              checkpoint_dir=ck, checkpoint_every=4, journal=lj)
    standby = _reg()
    lease_path = tmp_path / "lease"
    llease = Lease(lease_path, "L", timeout_s=30.0)
    assert llease.try_acquire()
    slease = Lease(lease_path, "S", timeout_s=1e9)
    stop = threading.Event()
    sj = TickJournal(tmp_path / "sj")
    follower = StandbyFollower(standby, sj, lease=slease, port=0,
                               alert_path=str(tmp_path / "alerts.jsonl"),
                               checkpoint_dir=ck, stop_event=stop)
    t = threading.Thread(target=follower.run, daemon=True)
    t.start()
    deadline = time.monotonic() + 20
    while follower.address is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert follower.address is not None
    # the leader races ahead DISCONNECTED; compaction drops tick 8
    live_loop(lambda k: _row(7, 8 + k, 4), leader, n_ticks=12,
              cadence_s=0.0, alert_path=str(tmp_path / "alerts.jsonl"),
              checkpoint_dir=ck, checkpoint_every=4, journal=lj)
    assert first_journal_tick(tmp_path / "lj") > 8, \
        "compaction never dropped the standby's position — shrink " \
        "segment_bytes or grow the run"
    sender = ReplicationSender(follower.address, lj,
                               checkpoint_dir=ck).start()
    lj.tee, lj.compact_floor = sender.tee, sender.compact_floor
    live_loop(lambda k: _row(7, 20 + k, 4), leader, n_ticks=4,
              cadence_s=0.0, alert_path=str(tmp_path / "alerts.jsonl"),
              checkpoint_dir=ck, checkpoint_every=4, journal=lj)
    deadline = time.monotonic() + 30
    while follower.expected < 24 and time.monotonic() < deadline:
        time.sleep(0.01)
    lj.close()
    sender.close()
    stop.set()
    t.join(timeout=20)
    sj.close()
    assert sender.snapshot_fallbacks >= 1
    assert follower.expected == 24
    _assert_groups_equal(leader, standby)


def test_follower_discards_divergent_local_tail(tmp_path):
    # a returning standby whose own journal extends past the adopted
    # checkpoints (the pre-failover timeline) must WIPE it and re-sync
    # from the stream, never replay it
    reg = _reg()
    ck = str(tmp_path / "ck")
    lj = TickJournal(tmp_path / "lj")
    live_loop(lambda k: _row(7, k, 4), reg, n_ticks=6, cadence_s=0.0,
              checkpoint_dir=ck, checkpoint_every=3, journal=lj)
    lj.close()
    # the standby's local mirror claims MORE ticks than the shared
    # checkpoints record (orphaned pre-failover rows)
    sj = TickJournal(tmp_path / "sj")
    for k in range(10):
        sj.append_tick(k, 100 + k, np.arange(4, dtype=np.float32))
    standby = _reg()
    slease = Lease(tmp_path / "lease2", "S", timeout_s=1e9)
    follower = StandbyFollower(standby, sj, lease=slease, port=0,
                               checkpoint_dir=ck)
    follower._catch_up()
    assert follower.expected == 6  # the checkpoints' position, not 10
    assert sj.next_tick == 0  # the divergent mirror was wiped
    sj.close()


def test_follower_never_adopts_a_checkpoint_swapped_under_its_read(
        tmp_path, monkeypatch):
    """The standby reads the shared checkpoints while the LIVE leader
    saves to them. `load_group` reads `meta.json` (the position) and then
    the state tree, by path; the leader's atomic swap between the two
    hands it round 3's position over round 6's state, no error raised, and
    the stream then feeds the group rows 3..5 a second time — the standby
    that takes over has diverged (what `scripts/failover_soak.py` reported
    as "state diverges" on a busy host, group 0 off from the takeover tick
    on: ISSUE 50). The swap is made to happen there; the adoption has to
    notice and read again."""
    import orbax.checkpoint as ocp

    ck = str(tmp_path / "ck")
    leader = _reg()
    lj = TickJournal(tmp_path / "lj")
    kw = dict(cadence_s=0.0, checkpoint_dir=ck, checkpoint_every=3,
              journal=lj)
    live_loop(lambda k: _row(7, k, 4), leader, n_ticks=3, **kw)
    restore = ocp.PyTreeCheckpointer.restore
    swapped = []

    def restore_after_the_leaders_next_round(self, *a, **k):
        if not swapped:  # meta.json is read; the state tree is not yet
            swapped.append(True)
            live_loop(lambda k: _row(7, 3 + k, 4), leader, n_ticks=3, **kw)
        return restore(self, *a, **k)

    monkeypatch.setattr(ocp.PyTreeCheckpointer, "restore",
                        restore_after_the_leaders_next_round)
    sj = TickJournal(tmp_path / "sj")
    standby = _reg()
    follower = StandbyFollower(
        standby, sj, lease=Lease(tmp_path / "lease", "S", timeout_s=1e9),
        port=0, checkpoint_dir=ck)
    follower._catch_up()
    lj.close()
    sj.close()
    assert swapped
    assert follower.gpos == [6, 6]
    _assert_groups_equal(leader, standby)


@pytest.mark.parametrize("adopted", [
    "one_round",
    pytest.param("torn", marks=pytest.mark.xfail(
        strict=True,
        reason="ROADMAP D17: _promote stamps every group with min(gpos), "
               "so the group a round ahead is fed its rows twice")),
])
def test_promotion_checkpoints_each_group_at_its_own_position(
        tmp_path, adopted):
    """A standby promoted while still catching up. The shared checkpoints
    were adopted TORN — one group from the round at tick 3, the other
    from the round at tick 6, as happens when the leader is mid-round —
    which `_adopt_checkpoints` allows because the stream converges the
    groups; a promotion BEFORE that has to record each group where it
    stands, or the leader that resumes from the takeover checkpoint
    applies rows 3..5 to a group that already has them and diverges from
    the fault-free run (what `scripts/failover_soak.py` reported as
    "state diverges" on a starved host, ISSUE 50). Adopted from one
    round, the takeover tick is every group's."""
    import shutil

    from rtap_tpu.service.checkpoint import load_group
    from rtap_tpu.service.shardpath import group_checkpoint_path

    ck = str(tmp_path / "ck")
    reg = _reg()
    lj = TickJournal(tmp_path / "lj")
    kw = dict(cadence_s=0.0, checkpoint_dir=ck, checkpoint_every=3,
              journal=lj)
    live_loop(lambda k: _row(7, k, 4), reg, n_ticks=3, **kw)
    if adopted == "torn":
        shutil.copytree(group_checkpoint_path(ck, 0), tmp_path / "round3")
    live_loop(lambda k: _row(7, 3 + k, 4), reg, n_ticks=3, **kw)
    lj.close()
    if adopted == "torn":
        shutil.rmtree(group_checkpoint_path(ck, 0))
        shutil.copytree(tmp_path / "round3", group_checkpoint_path(ck, 0))
    sj = TickJournal(tmp_path / "sj")
    lease = Lease(tmp_path / "lease", "S", timeout_s=30.0)
    follower = StandbyFollower(_reg(), sj, lease=lease, port=0,
                               checkpoint_dir=ck)
    follower._catch_up()
    stood_at = list(follower.gpos)
    assert stood_at == ([3, 6] if adopted == "torn" else [6, 6])
    assert lease.try_acquire()
    follower._promote(0.0)
    sj.close()
    assert [load_group(group_checkpoint_path(ck, gi)).resume_journal_tick
            for gi in range(2)] == stood_at


# ----------------------------------------------------- writer fencing
def test_alert_writer_fence_refuses_writes(tmp_path):
    from rtap_tpu.service.alerts import AlertWriter

    path = str(tmp_path / "a.jsonl")
    fenced = {"v": False}
    w = AlertWriter(path, fence=lambda: not fenced["v"])
    w.emit_batch(["s0"], np.array([1]), np.array([1.0]),
                 np.array([0.9]), np.array([-5.0]), np.array([True]),
                 group=0, tick=0)
    fenced["v"] = True
    w.emit_batch(["s0"], np.array([2]), np.array([1.0]),
                 np.array([0.9]), np.array([-5.0]), np.array([True]),
                 group=0, tick=1)
    w.emit_event({"event": "should_not_land"})
    w.close()
    lines = [ln for ln in open(path) if ln.strip()]
    assert len(lines) == 1
    assert json.loads(lines[0])["alert_id"] == "0:s0:0"
    assert w.fenced_drops == 2


def test_live_loop_breaks_and_skips_final_save_when_fenced(tmp_path):
    reg = _reg()
    lease_path = tmp_path / "lease"
    mine = Lease(lease_path, "L", timeout_s=30.0)
    assert mine.try_acquire()
    ck = str(tmp_path / "ck")

    def source(k):
        if k == 5:
            # a standby promotes mid-run: epoch bumps behind our back
            cur = json.loads(lease_path.read_text())
            cur["epoch"] += 1
            cur["owner"] = "usurper"
            cur["ts"] = time.time()
            lease_path.write_text(json.dumps(cur))
            # expire the still_mine() probe cache: at cadence 0 on a
            # fast host the remaining ticks can all land inside the
            # min(0.2, timeout/4) s cache window and the run finishes
            # un-fenced (observed-flake class, reproduced at HEAD) —
            # the test pins the FENCE logic, not the cache cadence
            mine._last_probe = -1e9
        return _row(7, k, 4)

    stats = live_loop(source, reg, n_ticks=20, cadence_s=0.0,
                      alert_path=str(tmp_path / "a.jsonl"),
                      checkpoint_dir=ck, checkpoint_every=50,
                      lease=mine)
    assert stats["fenced"] is True
    assert stats["ticks"] < 20
    # the fenced leader never wrote the shared checkpoint dir (no
    # periodic round was due, and the final save is fence-gated)
    assert not os.path.isdir(os.path.join(ck, "group0000"))


def test_serve_cli_has_replication_flags():
    # the flag surface is load-bearing for the runbook; pin the names
    import rtap_tpu.__main__ as cli

    src = open(cli.__file__).read()
    for flag in ("--replicate-to", "--standby", "--replicate-listen",
                 "--lease-file", "--lease-timeout"):
        assert flag in src


def test_lease_seen_epoch_floor_is_race_safe(tmp_path):
    """rtap-lint race-pass fix (ISSUE 12): read() updates the seen-epoch
    floor from BOTH the heartbeat thread (under self._lock) and unlocked
    main-side probes (is_stale/holder). Unguarded, the read-modify-write
    max() could REGRESS the floor (T2 loads the old floor, T1 stores a
    higher one, T2 stores its stale max) — and a regressed floor at a
    promotion whose lease read fails restarts epochs low and re-inverts
    the fence. The fix serializes the update under a dedicated lock;
    this hammer pins the floor's monotonicity under contention."""
    import sys

    path = tmp_path / "lease"
    lease = Lease(path, "B", timeout_s=5.0)
    stop = threading.Event()
    regressions = []

    def probe():
        last = 0
        while not stop.is_set():
            lease.read()
            cur = lease._seen_epoch
            if cur < last:
                regressions.append((last, cur))
                return
            last = cur

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # widen the interleaving window
    try:
        threads = [threading.Thread(target=probe, name=f"rtap-test-{i}")
                   for i in range(4)]
        for t in threads:
            t.start()
        for epoch in range(1, 300):
            path.write_text(json.dumps(
                {"epoch": epoch, "owner": "A", "ts": time.time()}))
            lease.read()
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    finally:
        sys.setswitchinterval(old_interval)
    assert not regressions, (
        f"seen-epoch floor regressed under concurrent reads: {regressions}")
    assert lease._seen_epoch == 299
