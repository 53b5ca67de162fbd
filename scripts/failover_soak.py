"""Failover soak: kill-9 the LEADER of a hot-standby pair; prove takeover.

ISSUE 8 acceptance surface. Two symmetric serve children share an alert
sink, a checkpoint dir, and a leadership lease; whichever holds the
lease runs the seeded deterministic feed as leader, journals every tick,
and ships the journal stream to the other (the standby), which applies
every tick through the normal scoring path and emits nothing. A seeded
killer SIGKILLs the CURRENT leader at journal-observed ticks — the
seeded tick or, if the standby is not yet WARM there (its mirror within
a tick of the leader's journal), the first later one where it is: the
drill is takeover by a hot standby, and a process restarted after the
last kill needs seconds to boot and catch up, more on a busy host.
(What a standby does when promoted from BEHIND is another case, with
its own test: tests/unit/test_replicate.py, ROADMAP D17.) The
standby promotes on lease staleness (bumping the fencing epoch,
splicing the alert stream exactly-once, checkpointing its warm fleet)
and the killed process is restarted as the new standby — roles swap per
kill. One extra round SIGSTOPs the leader instead: the standby promotes
while the old leader is merely paused, and on SIGCONT the zombie must
discover the fence, append NOTHING to the alert sink, and exit
``FENCED_RC``. The run FAILS (exit 5) unless:

- the final checkpoint state (every orbax leaf of every group) is
  BIT-IDENTICAL to a fault-free single-process run over the same
  seeded feed,
- the spliced alert stream is exactly-once vs the fault-free run —
  zero duplicated, zero lost ``alert_id``s, per-id records equal,
- every takeover detected within the tick budget
  (``standby_promoted.detect_ticks`` <= ``--takeover-budget``, default
  10),
- the SIGSTOP round's zombie leader exited ``FENCED_RC`` with its
  fence-dropped line count recorded (it provably appended nothing).

In-tree smoke: K=2 kills + the fence round at tiny config
(tests/integration/test_failover.py, cpu backend). Silicon: the queued
``r11_failover`` hw_session step.

Usage: python scripts/failover_soak.py --seed 0 --kills 2 [--streams 6]
       [--group-size 3] [--ticks 200] [--cadence 0.25]
       [--checkpoint-every 7] [--backend cpu] [--lease-timeout 1.0]
       [--workdir DIR] [--out report.json] [--no-fence-round]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import maybe_force_cpu, require_device  # noqa: E402
from scripts.fleet_verdict import (  # noqa: E402
    final_tick_check,
    promotion_epoch_truth,
    reconcile_alert_counters,
    takeover_sequence,
)

VERIFY_FAILED_EXIT = 5
INFRA_FAILED_EXIT = 3


def log(msg: str) -> None:
    print(f"[failover] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------- child
def run_child(args) -> int:
    """One HA serve-process lifetime: decide role from the lease, follow
    (standby) until promoted or stopped, then serve the remaining ticks
    of the total budget as leader — journaled, checkpointed, replicated
    to the peer, fenced by the lease. ``--ref`` runs the plain
    single-process reference instead (no lease, no replication)."""
    maybe_force_cpu()
    if args.backend == "tpu":
        require_device()  # no TPU and no explicit CPU choice -> fail here

    import threading

    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.resilience import (
        FENCED_RC,
        Lease,
        ReplicationSender,
        StandbyFollower,
        TickJournal,
    )
    from rtap_tpu.service.checkpoint import peek_resume_ticks
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    # warm orbax BEFORE touching the lease: its first import (tensorstore
    # C init) holds the GIL for seconds on a 1-core host, and a lease
    # heartbeat starved through the first checkpoint round would read as
    # a dead leader to the peer (a false takeover)
    import orbax.checkpoint  # noqa: F401

    w = args.workdir
    os.makedirs(w, exist_ok=True)
    alerts = os.path.join(w, "alerts.jsonl")
    ckdir = os.path.join(w, "ck")
    jdir = os.path.join(w, "journal" if args.ref
                        else f"journal-{args.name}")
    journal = TickJournal(jdir)

    ids = [f"n{i // 3}.m{i % 3}" for i in range(args.streams)]
    reg = StreamGroupRegistry(cluster_preset(), group_size=args.group_size,
                              backend=args.backend,
                              threshold=args.threshold, debounce=1)
    for sid in ids:
        reg.add_stream(sid)
    reg.finalize()

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())

    lease = None
    resume_sup = None
    promote_info = None
    fleet_pub = None
    if not args.ref and args.fleet_port:
        # fleet observability plane (ISSUE 19): this child is a member;
        # the parent's in-process aggregator reads the verdict evidence
        # (DOWN -> role_changed sequence, merged counters/SLO) through
        # the plane instead of scraping per-child artifacts. Push faster
        # than the takeover window so event ORDER is evidence.
        from rtap_tpu.fleet import FleetPublisher

        fleet_pub = FleetPublisher(
            ("127.0.0.1", args.fleet_port), args.name, role="standby",
            push_interval_s=max(0.02, args.cadence / 2))
    if not args.ref:
        lease = Lease(os.path.join(w, "lease"), owner=args.name,
                      timeout_s=args.lease_timeout)
        cur = lease.read()
        fresh_other = (cur is not None and cur.get("owner") != args.name
                       and not lease._stale(cur))
        # --follow pins the intended role: a child the harness spawned
        # as a standby must never sniff a momentarily-stale lease (the
        # live leader mid-GIL-stall under host load) and come up as a
        # second leader — it FOLLOWS, and earns leadership only through
        # the promotion path (which fences the other side properly)
        if args.follow or fresh_other or not lease.try_acquire():
            if fleet_pub is not None:
                fleet_pub.start()  # the standby phase is on the plane too
            follower = StandbyFollower(
                reg, journal, lease=lease, port=args.listen,
                alert_path=alerts, checkpoint_dir=ckdir,
                cadence_s=args.cadence, stop_event=stop)
            log(f"{args.name}: standby following on :{args.listen}")
            outcome = follower.run()
            if outcome == "stopped":
                journal.close()
                if fleet_pub is not None:
                    fleet_pub.close()  # orderly BYE: "left", not DOWN
                return 0
            resume_sup = follower.resume_suppression
            promote_info = {
                "detect_s": round(follower.promote_detect_s, 3),
                "epoch": lease.epoch,
                "re_emitted": follower.promote_re_emitted,
                "suppressed": follower.promote_suppressed,
            }
            log(f"{args.name}: PROMOTED at epoch {lease.epoch} "
                f"(detect {follower.promote_detect_s:.3f}s)")
        # leadership liveness = PROCESS alive: the heartbeat thread
        # keeps the lease fresh through multi-second checkpoint rounds
        lease.start_heartbeat()
        if fleet_pub is not None:
            # promotion (or immediate leadership): same member, new
            # role, the lease epoch the parent checks against truth.
            # start() is idempotent — the standby path already pushes.
            fleet_pub.set_role("leader", lease_epoch=lease.epoch)
            fleet_pub.start()

    base = max(journal.next_tick, peek_resume_ticks(ckdir))
    n_eff = max(0, args.ticks - base)
    if fleet_pub is not None:
        fleet_pub.set_tick_base(base)  # report journal-GLOBAL progress

    sender = None
    if not args.ref:
        sender = ReplicationSender(("127.0.0.1", args.peer), journal,
                                   checkpoint_dir=ckdir).start()
        journal.tee = sender.tee
        journal.compact_floor = sender.compact_floor

    def source(k: int):
        g = base + k  # the feed depends only on the GLOBAL tick
        rng = np.random.Generator(np.random.Philox(key=(args.seed, g)))
        v = (30 + 5 * rng.random(len(ids))).astype(np.float32)
        if args.spike_every and g % args.spike_every == 0:
            v[(g // args.spike_every) % len(ids)] += 30.0
        return v, 1_700_000_000 + g

    # SLO verdict (ISSUE 11): per-tick host latency — the seeded feed's
    # synthetic epoch rules out the wall-anchored detect SLO here
    # (docs/SLO.md clock contract). The replication-ack lag rides the
    # tracker as a first-class gauge while this child leads.
    latency = slo = None
    if args.slo != "off":
        from rtap_tpu.obs.slo import tick_slo_pair

        latency, slo = tick_slo_pair(args.cadence, args.slo)
        if sender is not None:
            latency.lag_providers["repl_ack_ticks"] = \
                lambda _t, _ts: sender.ack_lag_ticks()
        if fleet_pub is not None:
            fleet_pub.attach(latency=latency, slo=slo)
    stats = live_loop(
        source, reg, n_ticks=n_eff, cadence_s=args.cadence,
        alert_path=alerts, checkpoint_dir=ckdir,
        checkpoint_every=args.checkpoint_every, journal=journal,
        lease=lease, stop_event=stop, resume_suppression=resume_sup,
        latency=latency, slo=slo, fleet=fleet_pub)
    if sender is not None:
        sender.close()
        journal.tee = None
    if lease is not None:
        lease.stop_heartbeat()
    journal.close()
    if fleet_pub is not None:
        fleet_pub.close()  # final-state flush + orderly BYE
    line = {"name": "ref" if args.ref else args.name, "base": base,
            "ran": stats["ticks"], "alerts": stats["alerts"],
            "fenced": bool(stats.get("fenced")),
            "fenced_line_drops": stats.get("fenced_line_drops", 0),
            "promoted": promote_info,
            "slo": stats.get("slo"),
            "repl_ack_lag": (stats.get("latency") or {}).get("lags")}
    if args.stats_out:
        with open(args.stats_out, "a") as f:
            f.write(json.dumps(line) + "\n")
            f.flush()
    print(json.dumps(line))
    if stats.get("fenced"):
        return FENCED_RC
    return 0


# --------------------------------------------------------------- parent
def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def child_cmd(args, workdir: str, name: str | None = None,
              listen: int = 0, peer: int = 0, ref: bool = False,
              follow: bool = False) -> list[str]:
    # the reference is the feed's function, not the clock's: it runs
    # unpaced, and defends no latency SLO that pacing would define
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--workdir", workdir, "--seed", str(args.seed),
           "--ticks", str(args.ticks), "--streams", str(args.streams),
           "--group-size", str(args.group_size),
           "--cadence", "0" if ref else str(args.cadence),
           "--checkpoint-every", str(args.checkpoint_every),
           "--backend", args.backend, "--threshold", str(args.threshold),
           "--lease-timeout", str(args.lease_timeout),
           "--spike-every", str(args.spike_every),
           "--stats-out", os.path.join(workdir, "stats.jsonl")]
    if ref:
        cmd += ["--slo", "off", "--ref"]
    else:
        if args.slo is not None:
            cmd += ["--slo", args.slo]
        cmd += ["--name", name, "--listen", str(listen),
                "--peer", str(peer)]
        if follow:
            cmd.append("--follow")
        if getattr(args, "fleet_port", 0):
            cmd += ["--fleet-port", str(args.fleet_port)]
    return cmd


def _lease_owner(path: str) -> str | None:
    try:
        with open(path) as f:
            return json.load(f).get("owner")
    except (OSError, ValueError):
        return None


def standby_is_warm(mirror_tick: int, leader_tick: int,
                    left_at: int = -1) -> bool:
    """Is the standby following the leader closely enough that a takeover
    now is a HOT standby's? Its mirror (a row is mirrored, then scored)
    holds the leader's journal to within a tick — so every group has
    converged on the stream, whatever round of the shared checkpoints each
    was adopted from — and has gone past `left_at`, where the journal of
    the process's previous life ended: until the restarted standby has
    wiped or outrun that, the directory holds the old timeline."""
    return mirror_tick > left_at and mirror_tick >= leader_tick - 1


def _wait(cond, timeout_s: float, poll_s: float = 0.02) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(poll_s)
    return False


def fleet_verdict(agg, args, observed: list, fence_report,
                  promotions: list, stats_lines: list,
                  failures: list[str]) -> dict:
    """Judge the FLEET-OBSERVED story against the lease/journal truth
    (ISSUE 19): every takeover must appear on the plane as the old
    leader going DOWN (staleness — a SIGKILLed process sends no BYE)
    followed by a ``role_changed`` to leader on the successor; the
    fleet-observed promotion epochs must equal the alert stream's
    ``standby_promoted`` epochs; the budget's completion and the
    completing leader's alert count must be visible through merged
    fleet state alone. The individual checks live in
    scripts/fleet_verdict.py, shared with crash_soak and fleet_chaos."""
    members = agg.members_view()
    events = agg.events_view()
    snaps = agg.member_snaps()
    fl_slo = agg.fleet_slo()

    # the observed failover sequence, one anchor per scheduled takeover
    anchors = [(k["killed"], k["new_leader"], "kill") for k in observed]
    if fence_report:
        anchors.append((fence_report["paused"],
                        fence_report["new_leader"], "fence"))
    checks = takeover_sequence(events, anchors, failures)
    fleet_epochs = promotion_epoch_truth(events, promotions, failures)
    final_tick = final_tick_check(members, args.ticks - 1, failures)

    reconciled = {}
    for line in stats_lines:
        nm = line.get("name")
        if nm not in snaps or line.get("fenced"):
            continue  # a fenced zombie's counters are fence-dropped
        reconciled[nm] = reconcile_alert_counters(
            snaps[nm], line.get("alerts"), f"member {nm}", failures)

    # fleet SLO comes from MERGED sketches (never max-of-member-p99s)
    if args.slo != "off":
        slos = fl_slo.get("slos") or []
        if not slos:
            failures.append("fleet plane carries no merged SLO verdict "
                            "despite armed SLOs")
        elif any(v.get("observed_quantile_s") is None
                 for v in slos if v.get("samples")):
            failures.append("fleet SLO verdict lacks a merged-sketch "
                            "observed quantile")

    return {
        "members": [{k: m.get(k) for k in ("member", "state", "role",
                                           "lease_epoch", "tick",
                                           "snapshots")}
                    for m in members],
        "sequence": checks,
        "promotion_epochs": fleet_epochs,
        "final_tick": final_tick,
        "counters_reconciled": reconciled,
        "events_total": len(events),
        "slo": fl_slo,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kills", type=int, default=2,
                    help="SIGKILLs delivered to the CURRENT leader at "
                         "seeded journal-observed ticks (>= 2 for the "
                         "acceptance bar)")
    ap.add_argument("--streams", type=int, default=6)
    ap.add_argument("--group-size", type=int, default=3)
    ap.add_argument("--ticks", type=int, default=200,
                    help="TOTAL tick budget across takeovers; each one "
                         "waits for a warm standby, some 30 ticks of "
                         "0.25 s after a restart on an idle CPU host")
    ap.add_argument("--cadence", type=float, default=0.25,
                    help="tick cadence; the takeover budget is in TICKS "
                         "of this cadence, so very small values make "
                         "host scheduling jitter dominate the budget")
    ap.add_argument("--checkpoint-every", type=int, default=7)
    ap.add_argument("--backend", default="cpu")
    ap.add_argument("--threshold", type=float, default=-1e9,
                    help="floor default = every scored tick is an alert "
                         "line, the densest exactly-once check")
    ap.add_argument("--lease-timeout", type=float, default=None,
                    help="lease staleness before the standby promotes "
                         "(default: 4 * cadence — detection = timeout "
                         "+ heartbeat age + poll, which must land "
                         "inside the 10-tick takeover budget)")
    ap.add_argument("--takeover-budget", type=int, default=10,
                    help="max takeover detection latency in ticks")
    ap.add_argument("--spike-every", type=int, default=13)
    ap.add_argument("--slo", default=None, metavar="NAME=TARGET@pQ",
                    help="latency SLO every serving child defends and "
                         "the report records a verdict for (default: "
                         "tick=<cadence>s@p99; 'off' disables — see "
                         "docs/SLO.md clock contract for why detect "
                         "SLOs don't apply to the seeded feed)")
    ap.add_argument("--fence-round",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="add a SIGSTOP/SIGCONT round proving a paused "
                         "old leader is fenced out of the alert sink")
    ap.add_argument("--fleet",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="host a fleet aggregator in the parent and make "
                         "every HA child a fleet member: the takeover "
                         "verdict (leader DOWN -> standby promoted at "
                         "the successor epoch), merged counters, and "
                         "the fleet SLO are then read through the fleet "
                         "plane and judged against the lease/journal "
                         "truth (docs/FLEET.md)")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None, help="report JSON path")
    # child-mode flags
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--ref", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--follow", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--name", default="A", help=argparse.SUPPRESS)
    ap.add_argument("--listen", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--peer", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--fleet-port", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--stats-out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.lease_timeout is None:
        # detection after a death = 1.5 * timeout (the follower's
        # staleness-persistence grace) + heartbeat age (timeout/3)
        # + staleness poll + host scheduling jitter; 4 * cadence lands
        # at ~8 ticks of the 10-tick budget with jitter headroom, and
        # the grace absorbs single starved-heartbeat reads
        args.lease_timeout = 4 * args.cadence
    if args.child:
        return run_child(args)

    from rtap_tpu.resilience import FENCED_RC, last_journal_tick
    from scripts.crash_soak import compare_states, parse_alert_stream

    workdir = args.workdir or tempfile.mkdtemp(prefix="failover_soak_")
    ref_dir = os.path.join(workdir, "ref")
    ha_dir = os.path.join(workdir, "ha")
    os.makedirs(ref_dir, exist_ok=True)
    os.makedirs(ha_dir, exist_ok=True)
    t_all = time.monotonic()
    failures: list[str] = []

    # 1. fault-free single-process reference over the identical feed
    log(f"reference run ({args.ticks} ticks, {args.streams} streams, "
        f"backend {args.backend})")
    rc = subprocess.run(child_cmd(args, ref_dir, ref=True)).returncode
    if rc != 0:
        log(f"FATAL: reference run failed rc={rc}")
        return INFRA_FAILED_EXIT

    # 2. the HA pair: A first (acquires the lease), then B (standby).
    # The parent hosts the fleet aggregator IN-PROCESS (Python API, no
    # HTTP hop): verdict evidence arrives through the plane.
    agg = None
    if args.fleet:
        from rtap_tpu.fleet import FleetAggregator

        agg = FleetAggregator(
            port=0,
            sweep_interval_s=max(0.02, min(0.2, args.cadence))).start()
        args.fleet_port = agg.port
        log(f"fleet aggregator on :{agg.port} (sweep "
            f"{agg.sweep_interval_s}s)")
    ports = dict(zip("AB", _free_ports(2)))
    lease_path = os.path.join(ha_dir, "lease")

    def spawn(name: str, follow: bool = True) -> subprocess.Popen:
        other = "B" if name == "A" else "A"
        return subprocess.Popen(child_cmd(
            args, ha_dir, name=name, listen=ports[name],
            peer=ports[other], follow=follow))

    procs = {"A": spawn("A", follow=False)}
    if not _wait(lambda: _lease_owner(lease_path) == "A", 120.0):
        log("FATAL: A never acquired the lease")
        return INFRA_FAILED_EXIT
    procs["B"] = spawn("B")
    unscheduled_fences: list[str] = []
    #: the last tick of each child's journal as its previous life left
    #: it: a restarted child's mirror says nothing of the new leader's
    #: stream until it has gone past that
    left_at: dict[str, int] = {}

    def journal_tick(name: str) -> int:
        return last_journal_tick(os.path.join(ha_dir, f"journal-{name}"))

    def respawn(name: str) -> None:
        left_at[name] = journal_tick(name)
        procs[name] = spawn(name)

    def reap() -> str | None:
        """An UNSCHEDULED fenced exit (rc FENCED_RC) is legitimate lease
        behavior under host scheduling jitter — a starved heartbeat read
        as a death, the standby promoted, the fence held, and the same
        exactly-once machinery governs the splice (it is verified by the
        final verdict either way). Respawn the fenced child as the new
        standby and carry on; any OTHER unexpected death is fatal."""
        from rtap_tpu.resilience import FENCED_RC as _F

        for nm, pp in list(procs.items()):
            rc = pp.poll()
            if rc is None or rc == 0:
                continue
            if rc == _F:
                unscheduled_fences.append(nm)
                log(f"{nm} fenced by an unscheduled takeover (host "
                    "jitter) — respawning as standby")
                respawn(nm)
            else:
                return f"child {nm} died unexpectedly rc={rc}"
        return None

    # 3. seeded kill schedule over the middle of the run + fence round
    rng = random.Random(args.seed)
    lo, hi = max(1, args.ticks // 5), max(2, args.ticks * 3 // 5)
    window = max(1, (hi - lo) // max(1, args.kills))
    targets = sorted(min(args.ticks - 8, lo + i * window
                         + rng.randrange(max(1, window // 2)))
                     for i in range(args.kills))
    fence_target = min(args.ticks - 4, args.ticks * 3 // 4) \
        if args.fence_round else None
    log(f"kill schedule (ticks): {targets}; fence round at "
        f"{fence_target}")

    observed: list[dict] = []
    fence_report: dict | None = None

    def leader_name() -> str | None:
        return _lease_owner(lease_path)

    def leader_reached(target: int) -> tuple[str, int] | None:
        """(leader, its journal's tick) once that is at or past `target`
        AND the other child is a warm standby."""
        name = leader_name()
        if name not in procs:
            return None
        at = journal_tick(name)
        other = "B" if name == "A" else "A"
        if at >= target and standby_is_warm(
                journal_tick(other), at, left_at.get(other, -1)):
            return name, at
        return None

    for target in targets:
        hit: dict = {}

        def reached():
            err = reap()
            if err is not None:
                hit["dead"] = err
                return True
            found = leader_reached(target)
            if found is not None:
                hit["name"], hit["tick"] = found
            return found is not None

        if not _wait(reached, 180.0):
            failures.append(f"killer missed target tick {target} "
                            f"(leader={leader_name()})")
            break
        if "dead" in hit:
            failures.append(hit["dead"])
            break
        name = hit["name"]
        p = procs[name]
        if p.poll() is not None:
            failures.append(
                f"leader {name} finished the budget before the standby "
                f"was warm for the kill at tick {target} — grow --ticks")
            break
        t_kill = time.monotonic()
        try:
            p.kill()  # SIGKILL: no cleanup, no flush
        except OSError:
            failures.append(f"could not SIGKILL leader {name}")
            break
        p.wait()
        log(f"killed leader {name} at tick {hit['tick']} (scheduled "
            f"{target})")
        if not _wait(lambda: leader_name() not in (None, name), 120.0):
            failures.append(
                f"standby never promoted after killing {name} at "
                f"tick {target}")
            break
        takeover_s = time.monotonic() - t_kill
        observed.append({"target": target, "tick": hit["tick"],
                         "killed": name, "new_leader": leader_name(),
                         "takeover_wall_s": round(takeover_s, 3)})
        # the killed process rejoins as the new standby
        respawn(name)

    # 4. fence round: pause the leader, let the standby promote, resume
    # the zombie — it must fence itself out and exit FENCED_RC
    if args.fence_round and not failures:
        hit = {}

        def reached_f():
            err = reap()
            if err is not None:
                hit["dead"] = err
                return True
            found = leader_reached(fence_target)
            if found is not None:
                hit["name"], hit["tick"] = found
            return found is not None

        if not _wait(reached_f, 180.0):
            failures.append(f"fence round missed target tick "
                            f"{fence_target} (leader={leader_name()})")
        elif "dead" in hit:
            failures.append(hit["dead"])
        elif procs[hit["name"]].poll() is not None:
            failures.append(
                f"leader {hit['name']} finished the budget before the "
                "standby was warm for the fence round — grow --ticks")
        else:
            name = hit["name"]
            p = procs[name]
            os.kill(p.pid, signal.SIGSTOP)
            log(f"SIGSTOPped leader {name} at tick {hit['tick']} "
                f"(scheduled {fence_target})")
            promoted = _wait(lambda: leader_name() not in (None, name),
                             120.0)
            os.kill(p.pid, signal.SIGCONT)
            if not promoted:
                failures.append("standby never promoted during the "
                                "fence round")
            else:
                try:
                    rc = p.wait(timeout=120.0)
                except subprocess.TimeoutExpired:
                    p.kill()
                    rc = p.wait()
                    failures.append(
                        f"paused old leader {name} never exited after "
                        "SIGCONT (fence did not bite)")
                fence_report = {"paused": name, "rc": rc,
                                "tick": hit["tick"],
                                "new_leader": leader_name()}
                if rc != FENCED_RC:
                    failures.append(
                        f"woken old leader {name} exited rc={rc}, "
                        f"expected FENCED_RC={FENCED_RC}")
                respawn(name)

    # 5. completion: the leader finishing the budget exits 0; stop the
    # remaining standby (SIGTERM -> orderly "stopped")
    done: dict = {}

    def budget_done():
        err = reap()
        if err is not None:
            done["err"] = err
            return True
        for name, p in procs.items():
            if p.poll() == 0:
                done["name"] = name
                return True
        return False

    if not _wait(budget_done, 300.0, poll_s=0.05):
        failures.append("no child completed the total tick budget")
    elif "err" in done:
        failures.append(done["err"])
    for name, p in procs.items():
        if p.poll() is None:
            p.terminate()
            try:
                p.wait(timeout=60.0)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                failures.append(f"standby {name} ignored SIGTERM")

    # 6. verdict
    ref_alerts = parse_alert_stream(os.path.join(ref_dir, "alerts.jsonl"))
    got_alerts = parse_alert_stream(os.path.join(ha_dir, "alerts.jsonl"))
    if got_alerts["dup"]:
        failures.append(f"{len(got_alerts['dup'])} DUPLICATED "
                        f"alert_id(s): {got_alerts['dup'][:5]}")
    ref_ids = set(ref_alerts["alerts"])
    got_ids = set(got_alerts["alerts"])
    lost = sorted(ref_ids - got_ids)
    extra = sorted(got_ids - ref_ids)
    if lost:
        failures.append(f"{len(lost)} LOST alert_id(s): {lost[:5]}")
    if extra:
        failures.append(f"{len(extra)} EXTRA alert_id(s): {extra[:5]}")
    mismatched = [aid for aid in (ref_ids & got_ids)
                  if ref_alerts["alerts"][aid] != got_alerts["alerts"][aid]]
    if mismatched:
        failures.append(f"{len(mismatched)} alert record(s) differ: "
                        f"{mismatched[:5]}")
    if not ref_ids:
        failures.append("reference run emitted zero alerts — the soak "
                        "proves nothing (lower --threshold)")
    leaves = compare_states(os.path.join(ref_dir, "ck"),
                            os.path.join(ha_dir, "ck"), failures)
    promotions = [e for e in got_alerts["events"]
                  if e.get("event") == "standby_promoted"]
    # budget check anchored to the SCHEDULED takeovers: each kill and
    # the fence round must have a promotion near the tick it was
    # delivered at, detected within budget. Unscheduled jitter-driven
    # promotions (see reap()) are reported but not budget-judged — the
    # exactly-once and state verdicts above govern them.
    anchors = [(k["tick"], "kill") for k in observed]
    if fence_report:
        anchors.append((fence_report["tick"], "fence"))
    for target, kind in anchors:
        cand = [p for p in promotions
                if p.get("detect_ticks") is not None
                and abs(p["tick"] - target) <= args.takeover_budget + 6]
        if not cand:
            failures.append(f"no standby_promoted event near the {kind} "
                            f"at tick {target}")
            continue
        p = min(cand, key=lambda q: abs(q["tick"] - target))
        if p["detect_ticks"] > args.takeover_budget:
            failures.append(
                f"takeover at tick {p['tick']} ({kind} at {target}) "
                f"detected in {p['detect_ticks']} ticks — over the "
                f"{args.takeover_budget}-tick budget")
    fenced_lines = []
    stats_path = os.path.join(ha_dir, "stats.jsonl")
    if os.path.isfile(stats_path):
        with open(stats_path) as f:
            fenced_lines = [json.loads(ln) for ln in f if ln.strip()]
    fenced_stats = [s for s in fenced_lines if s.get("fenced")]
    if fence_report and not fenced_stats:
        failures.append("fence round ran but no child reported a fenced "
                        "exit in stats.jsonl")
    # the SLO verdict (ISSUE 11): the completing leader's verdict covers
    # the run's tail; every serving child's rides its own stats line
    slo_verdict = next(
        (s.get("slo") for s in reversed(fenced_lines) if s.get("slo")),
        None)

    # the fleet plane's verdict (ISSUE 19): the aggregator's observed
    # story judged against the lease/journal truth above, and the whole
    # merged state preserved as an artifact (scripts/fleet_report.py
    # pretty-prints it; tests replay assertions against it)
    fleetobs = None
    if agg is not None:
        fleetobs = fleet_verdict(agg, args, observed, fence_report,
                                 promotions, fenced_lines, failures)
        with open(os.path.join(ha_dir, "fleet_snapshot.json"), "w") as f:
            json.dump(agg.snapshot(), f, indent=2)
        agg.close()

    report = {
        "seed": args.seed,
        "kills_scheduled": targets,
        "kills": observed,
        "fence_round": fence_report,
        "ticks": args.ticks,
        "cadence_s": args.cadence,
        "lease_timeout_s": args.lease_timeout,
        "takeover_budget_ticks": args.takeover_budget,
        "promotions": [
            {k: e.get(k) for k in ("tick", "epoch", "detect_s",
                                   "detect_ticks", "re_emitted",
                                   "suppressed")}
            for e in promotions],
        "alert_ids": len(ref_ids),
        "duplicated": len(got_alerts["dup"]),
        "lost": len(lost),
        "extra": len(extra),
        "garbage_lines": got_alerts["garbage"],
        "state_leaves_compared": leaves,
        "completed_by": done.get("name"),
        "unscheduled_fences": unscheduled_fences,
        "fenced_exits": fenced_stats,
        "slo_verdict": slo_verdict,
        "fleetobs": fleetobs,
        "wall_s": round(time.monotonic() - t_all, 1),
        "verified": not failures,
        "failures": failures,
        "workdir": workdir,
    }
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report))
    if failures:
        for msg in failures:
            log(f"FAIL: {msg}")
        return VERIFY_FAILED_EXIT
    log(f"OK: {len(observed)} kill(s) + "
        f"{'1 fence round' if fence_report else 'no fence round'}, "
        f"{len(promotions)} promotion(s), {report['alert_ids']} alert "
        f"ids exactly-once, {leaves} state leaves bit-identical")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
