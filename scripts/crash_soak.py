"""Crash soak: SIGKILL serve at seeded ticks; prove exactly-once durability.

ISSUE 5 acceptance surface. A deterministic serve child (journal +
periodic checkpoints + dense alert stream) runs under the real
:class:`rtap_tpu.resilience.Supervisor` while a seeded killer SIGKILLs
it at K random ticks (progress observed through the journal itself —
the kill lands at a tick, not a wall time). The supervisor restarts the
child; each restart restores its newest checkpoint, replays the
journaled ticks past it through the normal scoring path, and suppresses
already-delivered alert ids. The run FAILS (exit 5) unless:

- the final model state (every group's checkpoint tree) is
  BIT-IDENTICAL to a fault-free run over the same seeded feed,
- the concatenated alert stream carries exactly the fault-free run's
  ``alert_id`` set — zero duplicated, zero lost — with per-id records
  equal,
- every scheduled kill actually landed (rc -9) and the supervised run
  still completed its total tick budget.

A torn journal tail from a kill mid-write is expected and must never
prevent startup (truncations are counted in the report).

In-tree smoke: K=2 kills at tiny config (tests/integration/
test_durability_soak.py). Silicon: K>=10 at 4096x1024 — the queued
``r8_crash_soak`` hw_session step, which also reports catch-up replay
latency.

Usage: python scripts/crash_soak.py --seed 0 --kills 2 [--streams 6]
       [--group-size 3] [--ticks 96] [--cadence 0.01]
       [--checkpoint-every 7] [--backend cpu] [--threshold -1e9]
       [--journal-fsync os] [--workdir DIR] [--out report.json]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import maybe_force_cpu, require_device  # noqa: E402
from scripts.fleet_verdict import (  # noqa: E402
    classify_downs,
    final_tick_check,
    reconcile_alert_counters,
)

VERIFY_FAILED_EXIT = 5
INFRA_FAILED_EXIT = 3


def log(msg: str) -> None:
    print(f"[crash] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------- child
def run_child(args) -> int:
    """One serve-process lifetime: recover the journal, resume the
    checkpoints, replay, then run the REMAINING ticks of the total
    budget over the seeded deterministic feed. Killed children leave
    their journal/checkpoints/alerts behind; completing children append
    a stats line to --stats-out."""
    maybe_force_cpu()
    if args.backend == "tpu":
        require_device()  # no TPU and no explicit CPU choice -> fail here

    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.resilience import ChaosEngine, ChaosSpec, TickJournal
    from rtap_tpu.resilience.journal import parse_fsync
    from rtap_tpu.service.checkpoint import peek_resume_ticks
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    w = args.workdir
    os.makedirs(w, exist_ok=True)
    policy, every_n = parse_fsync(args.journal_fsync)
    journal = TickJournal(os.path.join(w, "journal"), fsync=policy,
                          fsync_every=every_n)
    ckdir = os.path.join(w, "ck")
    base = max(journal.next_tick, peek_resume_ticks(ckdir))
    n_eff = max(0, args.ticks - base)

    ids = [f"n{i // 3}.m{i % 3}" for i in range(args.streams)]
    reg = StreamGroupRegistry(cluster_preset(), group_size=args.group_size,
                              backend=args.backend,
                              threshold=args.threshold, debounce=1)
    for sid in ids:
        reg.add_stream(sid)
    reg.finalize()

    chaos = None
    if args.spec:
        # the schedule is GLOBAL-tick-indexed; a restarted child shifts
        # it onto its local clock (fired faults drop out — in particular
        # the proc_exit that killed the previous incarnation)
        chaos = ChaosEngine(ChaosSpec.from_file(args.spec).shifted(base))

    fleet_pub = None
    if args.fleet_port:
        # fleet observability plane (ISSUE 19): every incarnation of the
        # supervised child is the SAME fleet member ("serve") — a SIGKILL
        # shows up as staleness DOWN (no BYE), the restart as a rejoin
        # carrying the new resume base as its run_epoch. The parent reads
        # restart evidence through the plane, not per-child artifacts.
        from rtap_tpu.fleet import FleetPublisher

        fleet_pub = FleetPublisher(
            ("127.0.0.1", args.fleet_port), "serve", role="leader",
            run_epoch=base,
            push_interval_s=max(0.02, args.cadence / 2)).start()
        fleet_pub.set_tick_base(base)

    def seeded_row(k: int):
        g = base + k  # the feed depends only on the GLOBAL tick
        rng = np.random.Generator(np.random.Philox(key=(args.seed, g)))
        v = (30 + 5 * rng.random(len(ids))).astype(np.float32)
        if args.spike_every and g % args.spike_every == 0:
            # deterministic anomaly spikes so realistic thresholds see
            # alert traffic too (the floor threshold alerts every tick)
            v[(g // args.spike_every) % len(ids)] += 30.0
        return v, 1_700_000_000 + g

    source = seeded_row
    if args.binary_ingest:
        # route the SAME deterministic rows through the binary ingest
        # path in-process (frames -> walker -> dispatch-table scatter),
        # so the journal takes the raw-FRAME write-ahead path and every
        # kill-9 restart replays THROUGH the frame decode — the ISSUE 7
        # durability soak. Loopback, not a socket: the soak's verdict
        # is bit-identity, which a paced network feeder cannot promise.
        from rtap_tpu.ingest import BinaryBatchSource
        from rtap_tpu.ingest.protocol import data_frame

        bsrc = BinaryBatchSource(reg.slot_map(), port=None)
        bcodes = bsrc._table.codes

        def source(k: int):
            v, ts = seeded_row(k)
            bsrc.feed_frames([data_frame(bcodes, v, ts)])
            return bsrc(k)

        # live_loop journals raw frames when the source exposes them
        source.take_tick_frames = bsrc.take_tick_frames

    # SLO verdict (ISSUE 11): the seeded feed runs on a synthetic epoch,
    # so the wall-clock-anchored detect SLO is meaningless here — the
    # crash soak contracts on per-tick HOST latency instead (docs/SLO.md
    # clock contract). Pure observation: the bit-identity verdict is
    # judged on alert RECORDS, which the tracker never touches.
    latency = slo = None
    if args.slo != "off":
        from rtap_tpu.obs.slo import tick_slo_pair

        latency, slo = tick_slo_pair(args.cadence, args.slo)
        if fleet_pub is not None:
            fleet_pub.attach(latency=latency, slo=slo)
    stats = live_loop(
        source, reg, n_ticks=n_eff, cadence_s=args.cadence,
        alert_path=os.path.join(w, "alerts.jsonl"),
        checkpoint_dir=ckdir, checkpoint_every=args.checkpoint_every,
        journal=journal, chaos=chaos, latency=latency, slo=slo,
        fleet=fleet_pub)
    journal.close()
    if fleet_pub is not None:
        fleet_pub.close()  # final-state flush + orderly BYE
    line = {"base": base, "ran": stats["ticks"],
            "alerts": stats["alerts"],
            "scored": stats["scored"],
            "journal": stats.get("journal", {}),
            "slo": stats.get("slo")}
    if args.stats_out:
        with open(args.stats_out, "a") as f:
            f.write(json.dumps(line) + "\n")
            f.flush()
    print(json.dumps(line))
    return 0


# --------------------------------------------------------------- parent
def child_cmd(args, workdir: str, spec: str | None,
              fleet_port: int = 0) -> list[str]:
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--workdir", workdir, "--seed", str(args.seed),
           "--ticks", str(args.ticks), "--streams", str(args.streams),
           "--group-size", str(args.group_size),
           "--cadence", str(args.cadence),
           "--checkpoint-every", str(args.checkpoint_every),
           "--backend", args.backend, "--threshold", str(args.threshold),
           "--journal-fsync", args.journal_fsync,
           "--spike-every", str(args.spike_every),
           "--stats-out", os.path.join(workdir, "stats.jsonl")]
    if args.slo is not None:
        cmd += ["--slo", args.slo]
    if args.binary_ingest:
        cmd.append("--binary-ingest")
    if spec:
        cmd += ["--spec", spec]
    if fleet_port:
        cmd += ["--fleet-port", str(fleet_port)]
    return cmd


def _killer(sup, journal_dir: str, targets: list[int], observed: list,
            failures: list[str]) -> None:
    """SIGKILL the supervised child each time the journal shows the next
    target tick has been ingested; record the tick actually observed.
    Progress is the journal's LAST TICK INDEX, not a record count — the
    count shrinks when checkpoint compaction drops segments, the index
    is monotonic across rotation and compaction."""
    from rtap_tpu.resilience import last_journal_tick

    for target in targets:
        deadline = time.monotonic() + 120.0
        killed = False
        while time.monotonic() < deadline:
            n = last_journal_tick(journal_dir)
            child = sup.child
            if n >= target and child is not None and child.poll() is None:
                deaths_before = sup.deaths
                try:
                    child.kill()  # SIGKILL: no cleanup, no flush
                except OSError:
                    break
                observed.append(n)
                # wait for the supervisor to register the death before
                # aiming at the next target
                death_deadline = time.monotonic() + 60.0
                while sup.deaths == deaths_before and \
                        time.monotonic() < death_deadline:
                    time.sleep(0.01)
                killed = True
                break
            time.sleep(0.02)
        if not killed:
            failures.append(
                f"killer missed target tick {target} (journal reached "
                f"{last_journal_tick(journal_dir)}; child finished "
                "first?)")
            return


def _load_checkpoints(ckdir: str) -> dict:
    import orbax.checkpoint as ocp

    out = {}
    for name in sorted(os.listdir(ckdir)):
        p = os.path.join(ckdir, name)
        if not name.startswith("group") or not os.path.isdir(p):
            continue
        with open(os.path.join(p, "meta.json")) as f:
            meta = json.load(f)
        with ocp.PyTreeCheckpointer() as ckptr:
            tree = ckptr.restore(os.path.join(p, "state"))
        out[name] = (meta, tree)
    return out


def _flat(tree, prefix=""):
    import numpy as np

    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flat(tree[k], f"{prefix}/{k}")
    else:
        yield prefix, np.asarray(tree)


def compare_states(ref_ck: str, got_ck: str, failures: list[str]) -> int:
    """Bitwise comparison of two checkpoint dirs' full state trees;
    returns leaves compared."""
    import numpy as np

    ref, got = _load_checkpoints(ref_ck), _load_checkpoints(got_ck)
    if sorted(ref) != sorted(got):
        failures.append(f"checkpoint groups differ: {sorted(ref)} vs "
                        f"{sorted(got)}")
        return 0
    leaves = 0
    for name in sorted(ref):
        rmeta, rtree = ref[name]
        gmeta, gtree = got[name]
        if rmeta["ticks"] != gmeta["ticks"]:
            failures.append(f"{name}: final tick cursor {gmeta['ticks']} "
                            f"!= fault-free {rmeta['ticks']}")
        rl, gl = dict(_flat(rtree)), dict(_flat(gtree))
        if sorted(rl) != sorted(gl):
            failures.append(f"{name}: state tree keys differ")
            continue
        for key in sorted(rl):
            leaves += 1
            a, b = rl[key], gl[key]
            equal = (a.shape == b.shape) and (
                np.array_equal(a, b, equal_nan=True)
                if a.dtype.kind in "fc" else np.array_equal(a, b))
            if not equal:
                failures.append(
                    f"{name}{key}: state diverges from the fault-free run")
    return leaves


def parse_alert_stream(path: str) -> dict:
    """Split a JSONL incident stream into alert records by alert_id,
    plus events, duplicates, and unparseable fragments (torn lines).
    Line walking rides the ONE shared tolerant iterator
    (service/alerts.iter_alert_records) so torn-fragment and
    event-vs-alert semantics can never drift from the serve stack's own
    resume scans (ISSUE 9 satellite)."""
    from rtap_tpu.service.alerts import iter_alert_records

    alerts: dict = {}
    dup: list[str] = []
    events: list[dict] = []
    garbage = 0
    for kind, rec in iter_alert_records(path):
        if kind == "garbage":
            garbage += 1  # torn fragment from a kill mid-write
            continue
        if kind == "event":
            events.append(rec)
            continue
        aid = rec.get("alert_id")
        if aid is None:
            garbage += 1
            continue
        if aid in alerts:
            dup.append(aid)
        alerts[aid] = rec
    return {"alerts": alerts, "dup": dup, "events": events,
            "garbage": garbage}


def fleet_verdict(agg, args, stats_path: str,
                  failures: list[str]) -> dict:
    """Judge the FLEET-OBSERVED restart story (ISSUE 19): every SIGKILL
    must appear on the plane as the member going DOWN by staleness (a
    kill-9'd process sends no BYE) then REJOINING when the supervisor's
    replacement re-HELLOs under the same name; the budget's completion
    and the completing incarnation's alert accounting must be readable
    through the plane alone. The individual checks live in
    scripts/fleet_verdict.py, shared with failover_soak and
    fleet_chaos."""
    events = agg.events_view()
    members = agg.members_view()
    snap = agg.member_snaps().get("serve") or {}
    serve_ev = [e for e in events if e["member"] == "serve"]
    rejoins = [e for e in serve_ev if e["event"] == "rejoined"]
    death_downs, flaps = classify_downs(serve_ev)
    if len(rejoins) != args.kills:
        failures.append(
            f"fleet plane saw {len(rejoins)} rejoin(s), expected one "
            f"per restart ({args.kills})")
    if death_downs != args.kills:
        failures.append(
            f"fleet plane saw {death_downs} death DOWN(s) (staleness "
            f"DOWN answered by a rejoin), scheduled {args.kills} "
            f"kill(s)")
    # each restart resumes FORWARD: the rejoin HELLOs carry the new
    # incarnation's resume base as run_epoch, which must be monotonic
    bases = [e.get("run_epoch") or 0 for e in rejoins]
    if bases != sorted(bases):
        failures.append(
            f"fleet-observed restart resume bases went backwards: "
            f"{bases}")
    final_tick = final_tick_check(members, args.ticks - 1, failures)
    # the completing incarnation's stats line counts every crossing it
    # SCORED; on the plane those split into emitted lines plus
    # resume-suppressed already-delivered ids — the sum closes the books
    reconciled = None
    last_line = None
    if os.path.isfile(stats_path):
        with open(stats_path) as f:
            for line in f:
                last_line = json.loads(line)
    if last_line is not None and snap:
        reconciled = reconcile_alert_counters(
            snap, last_line.get("alerts"), "the completing child",
            failures)
    return {
        "members": [{k: m.get(k) for k in ("member", "state", "role",
                                           "run_epoch", "tick",
                                           "snapshots")}
                    for m in members],
        "death_downs": death_downs,
        "stall_flaps": flaps,
        "rejoins": len(rejoins),
        "restart_bases": bases,
        "final_tick": final_tick,
        "counters_reconciled": reconciled,
        "events_total": len(events),
    }


def verify(args, ref_dir: str, crash_dir: str, sup, observed_kills: list,
           failures: list[str]) -> dict:
    ref_alerts = parse_alert_stream(os.path.join(ref_dir, "alerts.jsonl"))
    got_alerts = parse_alert_stream(os.path.join(crash_dir, "alerts.jsonl"))

    # exactly-once: zero duplicated, zero lost, records equal per id
    if got_alerts["dup"]:
        failures.append(
            f"{len(got_alerts['dup'])} DUPLICATED alert_id(s): "
            f"{got_alerts['dup'][:5]}")
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
        failures.append(
            f"{len(mismatched)} alert record(s) differ from the "
            f"fault-free run: {mismatched[:5]}")
    if not ref_ids:
        failures.append("fault-free run emitted zero alerts — the soak "
                        "proves nothing (lower --threshold)")

    # final state bit-identical
    leaves = compare_states(os.path.join(ref_dir, "ck"),
                            os.path.join(crash_dir, "ck"), failures)

    # every kill landed as SIGKILL and the budget completed
    if sup.deaths != args.kills:
        failures.append(f"supervisor saw {sup.deaths} death(s), "
                        f"scheduled {args.kills}")
    bad_sigs = [s for s in sup.kill_signals if s != 9]
    if bad_sigs:
        failures.append(f"non-SIGKILL deaths observed: {bad_sigs}")

    # catch-up accounting: EVERY restart's replay comes from the
    # incident stream's journal_replayed events (a killed child never
    # reaches its stats append — stats.jsonl sees only completing
    # children, which would under-report K-1 of K catch-ups)
    stats_path = os.path.join(crash_dir, "stats.jsonl")
    total_ran = 0
    slo_verdict = None
    if os.path.isfile(stats_path):
        with open(stats_path) as f:
            for line in f:
                s = json.loads(line)
                total_ran = max(total_ran, s["base"] + s["ran"])
                # the final completing child's verdict covers the run's
                # tail; per-restart verdicts ride each stats line
                slo_verdict = s.get("slo") or slo_verdict
    trunc_events = [e for e in got_alerts["events"]
                    if e.get("event") == "journal_tail_truncated"]
    replay_events = [e for e in got_alerts["events"]
                     if e.get("event") == "journal_replayed"]
    catch_up = [{"replayed_ticks": e.get("ticks"),
                 "from_tick": e.get("from_tick"),
                 "replay_seconds": e.get("seconds")}
                for e in replay_events]
    if args.kills and not replay_events:
        failures.append("no journal_replayed event on the incident "
                        "stream despite kills — recovery never ran?")
    return {
        "alert_ids": len(ref_ids),
        "alerts_crash_run": len(got_ids),
        "duplicated": len(got_alerts["dup"]),
        "lost": len(lost),
        "extra": len(extra),
        "garbage_lines": got_alerts["garbage"],
        "state_leaves_compared": leaves,
        "kills_observed_at_ticks": observed_kills,
        "deaths": sup.deaths,
        "kill_signals": sup.kill_signals,
        "total_ticks_completed": total_ran,
        "catch_up": catch_up,
        "journal_truncation_events": len(trunc_events),
        "journal_replay_events": len(replay_events),
        "slo_verdict": slo_verdict,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the feed, the spike schedule, and the "
                         "kill ticks; same seed = same soak")
    ap.add_argument("--kills", type=int, default=2,
                    help="SIGKILLs delivered at seeded ticks (K>=2 "
                         "in-tree smoke, K>=10 on silicon)")
    ap.add_argument("--streams", type=int, default=6)
    ap.add_argument("--group-size", type=int, default=3)
    ap.add_argument("--ticks", type=int, default=96,
                    help="TOTAL tick budget across restarts")
    ap.add_argument("--cadence", type=float, default=0.01)
    ap.add_argument("--checkpoint-every", type=int, default=7)
    ap.add_argument("--backend", default="cpu")
    ap.add_argument("--threshold", type=float, default=-1e9,
                    help="alert threshold; the floor default makes every "
                         "scored tick an alert line — the densest "
                         "exactly-once check. Silicon runs use a real "
                         "threshold + the seeded spikes")
    ap.add_argument("--journal-fsync", default="os")
    ap.add_argument("--binary-ingest", action="store_true",
                    help="feed every child through the RB1 binary ingest "
                         "path (in-process loopback): the journal write-"
                         "ahead becomes raw FRAME records and each "
                         "restart's catch-up replays through the frame "
                         "decode — same bit-identity + exactly-once "
                         "verdict, over the new path (docs/INGEST.md)")
    ap.add_argument("--spike-every", type=int, default=13)
    ap.add_argument("--slo", default=None, metavar="NAME=TARGET@pQ",
                    help="latency SLO the children defend and the report "
                         "records a verdict for (default: tick=<cadence>"
                         "s@p99 — per-tick host latency; the seeded feed "
                         "runs on a synthetic epoch so wall-anchored "
                         "detect SLOs don't apply here, docs/SLO.md). "
                         "'off' disables")
    ap.add_argument("--restart-backoff", type=float, default=0.05)
    ap.add_argument("--fleet", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run an in-process fleet aggregator and judge "
                         "the restart story through the fleet plane too "
                         "(staleness DOWN per kill, rejoin per restart, "
                         "merged counters reconcile — docs/FLEET.md)")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None, help="report JSON path")
    # child-mode flags
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--spec", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--stats-out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--fleet-port", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return run_child(args)

    from rtap_tpu.resilience import Supervisor

    workdir = args.workdir or tempfile.mkdtemp(prefix="crash_soak_")
    ref_dir = os.path.join(workdir, "ref")
    crash_dir = os.path.join(workdir, "crash")
    os.makedirs(ref_dir, exist_ok=True)
    os.makedirs(crash_dir, exist_ok=True)
    t_all = time.monotonic()

    # 1. fault-free reference over the identical seeded feed
    log(f"reference run ({args.ticks} ticks, {args.streams} streams, "
        f"backend {args.backend})")
    rc = subprocess.run(child_cmd(args, ref_dir, None)).returncode
    if rc != 0:
        log(f"FATAL: fault-free reference run failed rc={rc}")
        return INFRA_FAILED_EXIT

    # 2. seeded kill schedule: K ticks spread over the middle of the run
    rng = random.Random(args.seed)
    span = max(args.kills, args.ticks * 3 // 5)
    lo = max(1, args.ticks // 5)
    window = max(1, span // max(1, args.kills))
    targets = sorted(min(args.ticks - 2, lo + i * window
                         + rng.randrange(max(1, window // 2)))
                     for i in range(args.kills))
    log(f"kill schedule (ticks): {targets}")

    # 3. supervised crashy run (the parent's aggregator watches it
    # through the fleet plane: kills land as staleness DOWNs, restarts
    # as rejoins — the reference run stays off the plane so the fleet
    # story is the crash run's alone)
    agg = None
    if args.fleet:
        from rtap_tpu.fleet import FleetAggregator

        agg = FleetAggregator(
            port=0,
            sweep_interval_s=max(0.02, min(0.2, args.cadence))).start()
        log(f"fleet aggregator on :{agg.port}")
    sup = Supervisor(
        child_cmd(args, crash_dir, None,
                  fleet_port=agg.port if agg is not None else 0),
        restart_budget=args.kills + 2,
        backoff_base_s=args.restart_backoff,
        backoff_max_s=max(1.0, args.restart_backoff * 4),
        event_path=os.path.join(crash_dir, "alerts.jsonl"),
        log=log)
    failures: list[str] = []
    observed: list = []
    killer = threading.Thread(
        target=_killer,
        args=(sup, os.path.join(crash_dir, "journal"), targets, observed,
              failures),
        daemon=True)
    killer.start()
    rc = sup.run(install_signals=False)
    killer.join(timeout=10.0)
    if rc != 0:
        failures.append(f"supervised run ended rc={rc} "
                        f"(deaths={sup.deaths})")

    # 4. verdict
    report_body = verify(args, ref_dir, crash_dir, sup, observed, failures)
    if agg is not None:
        report_body["fleetobs"] = fleet_verdict(
            agg, args, os.path.join(crash_dir, "stats.jsonl"), failures)
        with open(os.path.join(crash_dir, "fleet_snapshot.json"),
                  "w") as f:
            json.dump(agg.snapshot(), f, indent=2)
        agg.close()
    report = {
        "seed": args.seed,
        "kills_scheduled": targets,
        "ticks": args.ticks,
        "streams": args.streams,
        "group_size": args.group_size,
        "backend": args.backend,
        "journal_fsync": args.journal_fsync,
        "wall_s": round(time.monotonic() - t_all, 1),
        **report_body,
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
    log(f"OK: {args.kills} kill(s) at ticks {observed}, "
        f"{report['alert_ids']} alert ids exactly-once, "
        f"{report['state_leaves_compared']} state leaves bit-identical")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
