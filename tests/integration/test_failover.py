"""ISSUE 8 acceptance: hot-standby failover, end to end.

(Named to sort after test_durability_soak/test_cli so the tier-1 870 s
dot-count window is untouched — these drills pay real process restarts.)

1. the failover soak smoke — ``scripts/failover_soak.py`` SIGKILLs the
   CURRENT leader of a live replicated pair twice at seeded
   journal-observed ticks, runs a SIGSTOP fence round, and its own
   verdict machinery proves: final checkpoint state bit-identical to a
   fault-free run (every orbax leaf), the spliced alert stream
   exactly-once, every takeover detected within the 10-tick budget,
   and the woken zombie leader fenced out of the alert sink
   (rc FENCED_RC, zero appends);
2. the serve CLI pair — ``serve --replicate-to`` / ``serve --standby``
   wired end to end: the standby mirrors the leader's journal
   byte-identically and stops cleanly on SIGTERM;
3. the flag-consistency gates (usage errors before backend init).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _env():
    env = {**os.environ, "RTAP_FORCE_CPU": "1"}
    return env


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_failover_soak_two_kills_and_fence_round(tmp_path):
    """The in-tree acceptance smoke: 2 SIGKILLs + 1 SIGSTOP fence round;
    the soak's exit code IS the verdict (5 = availability violated).

    The drill runs in real time (0.25 s ticks) and delivers each kill at
    its seeded tick or, where the standby is not yet warm there, at the
    first later tick where it is (a restarted child boots and catches up
    in some 30 ticks on an idle host, more beside five other test
    workers). 200 ticks leave the three takeovers that room; the kill
    that found no warm standby inside them fails the run and says so.
    What a standby does when promoted from BEHIND has its own case,
    tests/unit/test_replicate.py (ROADMAP D17)."""
    out = str(tmp_path / "report.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "failover_soak.py"),
         "--seed", "3", "--kills", "2", "--streams", "6",
         "--group-size", "3", "--ticks", "200", "--cadence", "0.25",
         "--checkpoint-every", "6", "--backend", "cpu",
         "--workdir", str(tmp_path / "w"), "--out", out],
        env=_env(), capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, \
        f"failover soak failed rc={proc.returncode}\n{proc.stderr[-4000:]}"
    report = json.load(open(out))
    assert report["verified"], report["failures"]
    assert len(report["kills"]) == 2
    # each kill at its seeded tick or later, never before
    assert all(k["tick"] >= k["target"] for k in report["kills"])
    # every SCHEDULED takeover inside the 10-tick detection budget —
    # report["verified"] above already enforced it per kill/fence
    # anchor; here just pin that all three takeovers left their record
    assert len(report["promotions"]) >= 3  # 2 kills + the fence round
    # exactly-once across every splice
    assert report["duplicated"] == 0 and report["lost"] == 0
    assert report["extra"] == 0
    assert report["alert_ids"] > 0
    # bit-identical final model state
    assert report["state_leaves_compared"] > 0
    # the fence proof: the paused old leader exited FENCED_RC and its
    # post-fence sink writes were refused (counted, never written)
    assert report["fence_round"] is not None
    assert report["fence_round"]["rc"] == 7
    assert report["fenced_exits"], "no child reported a fenced exit"
    assert all(s["fenced_line_drops"] >= 1 for s in report["fenced_exits"])


@pytest.mark.parametrize("mirror, leader, left_at, warm", [
    (59, 60, -1, True),    # a row behind: mirrored, being scored
    (60, 60, -1, True),
    (57, 60, -1, False),   # still backfilling
    (-1, 0, -1, False),    # no mirror yet (booting), leader at its first tick
    (47, 48, 47, False),   # the journal its last life left, not a mirror
    (49, 50, 47, True),    # ... outrun: these rows are the new leader's
], ids=["a_row_behind", "level", "backfilling", "booting",
        "last_lifes_journal", "outran_its_last_life"])
def test_the_killer_tells_a_warm_standby_from_a_cold_one(
        mirror, leader, left_at, warm):
    from scripts.failover_soak import standby_is_warm

    assert standby_is_warm(mirror, leader, left_at) is warm


def test_serve_cli_leader_standby_pair(tmp_path):
    """serve --replicate-to / --standby end to end: the standby mirrors
    the leader's journal byte-range exactly and SIGTERM stops it with
    an orderly stats line. (No producer pushes: NaN ticks — journal
    shipping is exercised regardless, every tick appends.)"""
    from rtap_tpu.resilience import last_journal_tick

    w = tmp_path
    port = _free_port()
    lease = str(w / "lease")
    # 25 ticks at 0.3 s = a ~7.5 s serving window: the standby child
    # pays its own interpreter+backend init AFTER the leader's (the
    # 1-core tier-1 host serializes them), and the leader's sender must
    # still be alive to connect+backfill when the listener comes up —
    # a 10x0.2 s window raced that init and flaked with an empty mirror
    common = ["--streams", "a,b,c", "--backend", "cpu", "--ticks", "25",
              "--cadence", "0.3", "--group-size", "3",
              "--checkpoint-dir", str(w / "ck"),
              "--alerts", str(w / "alerts.jsonl"),
              "--lease-file", lease, "--lease-timeout", "30"]
    leader = subprocess.Popen(
        [sys.executable, "-m", "rtap_tpu", "serve", *common,
         "--journal-dir", str(w / "jl"),
         "--replicate-to", f"127.0.0.1:{port}"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    # the standby joins once the leader holds the lease (a standby with
    # no lease at all would rightly promote itself)
    deadline = time.time() + 120
    while time.time() < deadline and not os.path.isfile(lease):
        time.sleep(0.05)
    assert os.path.isfile(lease), "leader never acquired the lease"
    standby = subprocess.Popen(
        [sys.executable, "-m", "rtap_tpu", "serve", *common, "--standby",
         "--journal-dir", str(w / "js"),
         "--replicate-listen", str(port)],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    lout, lerr = leader.communicate(timeout=300)
    assert leader.returncode == 0, f"leader failed:\n{lerr[-3000:]}"
    lstats = json.loads(lout.strip().splitlines()[-1])
    assert lstats["ticks"] == 25
    assert "replication" in lstats
    # give the mirror a beat to drain the tail, then stop the standby
    deadline = time.time() + 60
    while time.time() < deadline and \
            last_journal_tick(str(w / "js")) < 24:
        time.sleep(0.1)
    standby.send_signal(signal.SIGTERM)
    sout, serr = standby.communicate(timeout=300)
    assert standby.returncode == 0, f"standby failed:\n{serr[-3000:]}"
    sline = json.loads(sout.strip().splitlines()[-1])
    # either an orderly follow-stop, or (if the lease went stale first)
    # a zero-remaining promotion — both are clean exits with stats
    assert sline.get("stopped") or sline.get("promoted_from_standby")
    # the mirror reached the leader's last journaled tick
    assert last_journal_tick(str(w / "js")) == \
        last_journal_tick(str(w / "jl")) == 24


@pytest.mark.parametrize("argv,needle", [
    (["--standby"], "--standby needs"),
    (["--replicate-to", "127.0.0.1:1"], "add --journal-dir"),
    (["--journal-dir", "j", "--replicate-to", "127.0.0.1:1"],
     "needs --lease-file"),
    (["--journal-dir", "j", "--replicate-to", "127.0.0.1:1",
      "--lease-file", "l"], "needs --checkpoint-dir"),
    (["--replicate-listen", "7"], "add --standby"),
    (["--journal-dir", "j", "--replicate-to", "127.0.0.1:1",
      "--lease-file", "l", "--checkpoint-dir", "c",
      "--auto-register"], "FIXED fleet"),
    (["--journal-dir", "j", "--replicate-to", "127.0.0.1:1",
      "--lease-file", "l", "--checkpoint-dir", "c",
      "--alert-attribution"], "--alert-attribution under replication"),
])
def test_serve_replication_flag_gates(argv, needle):
    proc = subprocess.run(
        [sys.executable, "-m", "rtap_tpu", "serve", "--streams", "a",
         "--backend", "cpu", *argv],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert needle in proc.stderr


def test_chaos_soak_replication_mode(tmp_path):
    """ISSUE 8 satellite: the seeded wire fault kinds (conn_drop,
    stall_socket, corrupt_bytes) against a live leader/standby pair —
    chaos_soak's own verdict proves the standby stays bit-identical."""
    out = str(tmp_path / "report.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "chaos_soak.py"),
         "--replication", "--seed", "2", "--streams", "6",
         "--group-size", "3", "--ticks", "48", "--cadence", "0.02",
         "--rate", "0.15", "--backend", "cpu", "--checkpoint-every", "8",
         "--workdir", str(tmp_path / "w"), "--out", out],
        env=_env(), capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, \
        f"replication chaos soak failed rc={proc.returncode}\n" \
        f"{proc.stderr[-3000:]}"
    report = json.load(open(out))
    assert report["verified"], report["failures"]
    kinds = {e["kind"] for e in report["faults_injected"]}
    assert kinds == {"conn_drop", "stall_socket", "corrupt_bytes"}
    assert report["standby"]["applied_ticks"] == 48
    assert report["state_leaves_compared"] > 0
