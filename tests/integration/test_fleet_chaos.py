"""ISSUE 20 acceptance: the control plane + the fleet-wide chaos drill.

(Named to sort after test_cli/test_failover so the tier-1 dot-count
window is untouched — the drill smoke pays real process restarts and is
marked slow; the epoch-recovery and usage-error pins are cheap and run
in tier 1.)

1. the kill-9 epoch pin — a control plane restarted from its
   write-ahead journal can NEVER grant an epoch <= one it already
   granted, including with a torn garbage tail on the journal;
2. the control CLI flag-consistency gates (usage errors before backend
   init, exit 2 + message — the same contract as every serve flag);
3. the fleet chaos drill smoke — ``scripts/fleet_chaos.py`` at tiny
   config: 2 leader SIGKILLs + 1 standby SIGKILL + 1 control-plane
   SIGKILL + 1 SIGSTOP fence round + 1 rolling drain, verdict through
   the fleet plane vs journal/lease ground truth.
"""

import json
import os
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


# ------------------------------------------------- epoch recovery pin --
def test_kill9_control_plane_never_regrants_an_epoch(tmp_path):
    """The acceptance regression: grants are journaled write-ahead
    (fsync before the reply), so a plane that dies WITHOUT any orderly
    shutdown and restarts from the same journal dir must floor its next
    grant STRICTLY ABOVE every epoch it ever handed out — a re-granted
    epoch would invert the fence for a zombie holding the original."""
    from rtap_tpu.fleet.control import ControlLease, ControlPlane
    from rtap_tpu.obs.metrics import TelemetryRegistry

    jdir = str(tmp_path / "ctrl")
    timeout_s = 0.2
    plane = ControlPlane(jdir, port=0, lease_timeout_s=timeout_s,
                         registry=TelemetryRegistry()).start()
    addr = plane.address

    a = ControlLease(addr, "A", shard=5, timeout_s=timeout_s,
                     registry=TelemetryRegistry())
    assert a.try_acquire() and a.epoch == 1
    a.release()
    b = ControlLease(addr, "B", shard=5, timeout_s=timeout_s,
                     registry=TelemetryRegistry())
    assert b.try_acquire() and b.epoch == 2

    # kill-9 semantics: no release, no orderly flush — the socket just
    # goes away with B's lease live in the table
    plane.close()

    # a torn tail (the plane died mid-append) must not poison recovery
    with open(os.path.join(jdir, "control.journal"), "ab") as f:
        f.write(b"\x13\x37torn-garbage")

    plane2 = ControlPlane(jdir, port=0, lease_timeout_s=timeout_s,
                          registry=TelemetryRegistry()).start()
    try:
        assert plane2.recovered_shards == 1
        # boot grace: a takeover straight after restart is DENIED until
        # one lease timeout has passed (the live holder gets a chance
        # to re-stamp before anyone steals)
        c = ControlLease(plane2.address, "C", shard=5,
                         timeout_s=timeout_s,
                         registry=TelemetryRegistry())
        assert not c.try_acquire()
        deadline = time.monotonic() + 20 * timeout_s
        while time.monotonic() < deadline and not c.try_acquire():
            time.sleep(timeout_s / 2)
        # THE invariant: strictly above every epoch ever granted,
        # even though the grant table itself died with the process
        assert c.epoch == 3, \
            f"restarted plane granted epoch {c.epoch}, expected 3"
    finally:
        plane2.close()


def test_control_journal_reader_reports_grants(tmp_path):
    """read_control_journal is the soak's ground truth: grants land in
    order with their epochs, and release/drain marks are recorded."""
    from rtap_tpu.fleet.control import (
        ControlLease,
        ControlPlane,
        control_drain,
        read_control_journal,
    )
    from rtap_tpu.obs.metrics import TelemetryRegistry

    jdir = str(tmp_path / "ctrl")
    plane = ControlPlane(jdir, port=0, lease_timeout_s=0.5,
                         registry=TelemetryRegistry()).start()
    try:
        a = ControlLease(plane.address, "A", shard=0, timeout_s=0.5,
                         registry=TelemetryRegistry())
        assert a.try_acquire()
        assert control_drain(plane.address, 0)
        a.release()
    finally:
        plane.close()
    kinds = [(r["kind"], r.get("epoch")) for r in
             read_control_journal(jdir)]
    assert kinds == [("grant", 1), ("drain", None), ("release", None)]


# ----------------------------------------------------- CLI usage gates --
def _cli(*args):
    return subprocess.run([sys.executable, "-m", "rtap_tpu", *args],
                          cwd=REPO, env=_env(), capture_output=True,
                          text=True, timeout=120)


def test_serve_control_flag_usage_errors(tmp_path):
    """Every --control-* gate fires BEFORE backend init (exit 2 +
    message), the same contract as the --fleet-* flags (ISSUE 19)."""
    p = _cli("serve", "--streams", "a", "--control-listen", "0")
    assert p.returncode == 2 and "--control-journal" in p.stderr
    p = _cli("serve", "--streams", "a",
             "--control-journal", str(tmp_path / "j"))
    assert p.returncode == 2 and "--control-listen" in p.stderr
    p = _cli("serve", "--control-only")
    assert p.returncode == 2 and "--control-listen" in p.stderr
    # --streams stays mandatory for every DATA-plane serve
    p = _cli("serve")
    assert p.returncode == 2 and "--streams is required" in p.stderr
    p = _cli("serve", "--streams", "a", "--control-join", "nocolon")
    assert p.returncode == 2 and "bad --control-join" in p.stderr
    p = _cli("serve", "--streams", "a", "--control-join", "host:99999")
    assert p.returncode == 2 and "bad --control-join" in p.stderr
    # one lease authority per process
    p = _cli("serve", "--streams", "a", "--control-join", ":9001",
             "--lease-file", str(tmp_path / "lease"))
    assert p.returncode == 2 and "exclusive" in p.stderr
    p = _cli("serve", "--streams", "a", "--control-grace", "5")
    assert p.returncode == 2 and "--control-join" in p.stderr
    p = _cli("serve", "--streams", "a", "--control-join", ":9001",
             "--control-grace", "0")
    assert p.returncode == 2 and "must be > 0" in p.stderr
    p = _cli("serve", "--streams", "a", "--shard", "-1")
    assert p.returncode == 2 and "--shard" in p.stderr


# ------------------------------------------------------- drill smoke --
@pytest.mark.slow
def test_fleet_chaos_drill_smoke(tmp_path):
    """The in-tree acceptance smoke at tiny config; the drill's exit
    code IS the verdict (5 = an availability/exactness bar failed)."""
    out = str(tmp_path / "report.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "fleet_chaos.py"),
         "--seed", "3", "--ticks", "120", "--cadence", "0.1",
         "--streams", "4", "--group-size", "2",
         "--workdir", str(tmp_path / "w"), "--out", out],
        env=_env(), capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"fleet chaos failed rc={proc.returncode}\n{proc.stderr[-4000:]}"
    report = json.load(open(out))
    assert report["verified"], report["failures"]
    assert len(report["leader_kills"]) == 2
    assert report["standby_kill"] is not None
    assert report["control_outage"]["leaders_survived"]
    assert report["fence_round"]["rc"] == 7
    assert report["drain_round"]["rc"] == 0
    for s in report["shards_verdict"]:
        assert s["duplicated"] == 0 and s["lost"] == 0 and s["extra"] == 0
        assert s["alert_ids"] > 0 and s["state_leaves_compared"] > 0
    for eps in report["control_journal"]["grants_per_shard"].values():
        assert eps == sorted(set(eps)), eps
    assert report["degraded_ticks_stats"] > 0
