"""Chaos soak: a seeded fault schedule against the REAL live loop.

ISSUE 2 acceptance surface: every resilience path — source faults, group
quarantine + checkpoint restore, alert-sink quarantine, checkpoint-save
breaker — exercised end-to-end by deterministic injection, with a
machine-checked verdict:

- ``--seed N`` fully determines the fault schedule
  (``ChaosSpec.generate`` uses a private ``random.Random(seed)``); the
  report carries the schedule digest so two runs are comparable by eye.
- The run FAILS (exit 5) if any group's streams silently stopped being
  scored while unquarantined: per-group scored counts from the loop's
  ``scored_by_group`` stats must exactly match the unquarantined tick
  intervals reconstructed from the ``group_quarantined`` /
  ``group_restored`` events on the alert stream. Quarantine is allowed
  (that is the mechanism working); silence is not.

``--supervise`` (ISSUE 5) runs the soak OUT of process instead: the
seeded schedule gains ``proc_exit`` faults (abrupt ``os._exit`` at tick
boundaries) and the child — ``scripts/crash_soak.py --child``, the
journaled + checkpointed serve runner — flies under the real
:class:`rtap_tpu.resilience.Supervisor`. The verdict checks the
supervisor restarted the child once per scheduled kill, the run still
completed its total tick budget, journal recovery actually ran
(``journal_replayed`` events on the incident stream), and the alert
stream carries zero duplicated ``alert_id``s.

``--topology-burst`` (ISSUE 9) schedules one explicit ``topology_burst``
fault — the source floods two adjacent nodes' streams (spanning multiple
serve groups) with a correlated value burst — alongside seeded
``source_timeout`` background noise, with topology-aware incident
correlation armed (``TopologyMap.infer`` over the soak's node naming).
The verdict: exactly ONE cluster-level incident pages (not N per-stream
alerts), its blast-radius node set is exactly the flooded nodes, and
every member alert_id is a real alert line on the stream.

``--replication`` (ISSUE 8) runs the seeded schedule against a LIVE
leader/standby pair instead: a journaled leader loop ships every append
to an in-process :class:`~rtap_tpu.resilience.StandbyFollower` over a
real socket while the ISSUE 8 network fault kinds — ``conn_drop``,
``stall_socket``, ``corrupt_bytes`` — fire on the wire at seeded
record ticks (``ChaosEngine.on_wire``). The verdict: the standby's
final model state is BIT-IDENTICAL to the leader's (every checkpoint
leaf) despite the faults, the standby applied every tick, and each
scheduled wire fault actually injected.

Usage: python scripts/chaos_soak.py --seed 1 [--streams 12]
       [--group-size 4] [--ticks 120] [--cadence 0.05] [--rate 0.08]
       [--backend tpu] [--out reports/chaos_soak.json]
       [--supervise --kills 2] [--replication]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import maybe_force_cpu, require_device  # noqa: E402

VERIFY_FAILED_EXIT = 5


def log(msg: str) -> None:
    print(f"[chaos] {msg}", file=sys.stderr, flush=True)


def _unquarantined_intervals(events: list[dict], n_groups: int,
                             ticks: int) -> list[list[tuple[int, int]]]:
    """Per group, the [start, end) tick intervals it was being scored,
    reconstructed from the alert stream's quarantine/restore events."""
    start = [0] * n_groups
    active = [True] * n_groups
    intervals: list[list[tuple[int, int]]] = [[] for _ in range(n_groups)]
    for e in events:
        g = e.get("group")
        if g is None or not 0 <= g < n_groups:
            continue
        if e["event"] == "group_quarantined" and active[g]:
            intervals[g].append((start[g], e["tick"]))
            active[g] = False
        elif e["event"] == "group_restored" and not active[g]:
            start[g] = e["tick"]
            active[g] = True
    for g in range(n_groups):
        if active[g]:
            intervals[g].append((start[g], ticks))
    return intervals


def run_supervised(args) -> int:
    """`--supervise`: seeded proc_exit kills + source/sink faults against
    the journaled serve child under the real Supervisor."""
    import random

    from rtap_tpu.resilience import ChaosSpec, Fault, Supervisor

    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_supervise_")
    os.makedirs(workdir, exist_ok=True)
    n_groups = -(-args.streams // args.group_size)
    # in-process-safe kinds ride along at the normal rate; group-killing
    # kinds stay out (a quarantined group across a restart boundary is a
    # different study — the journal replays it back to health anyway)
    base = ChaosSpec.generate(
        seed=args.seed, n_ticks=args.ticks, n_groups=n_groups,
        rate=args.rate,
        kinds=("source_timeout", "source_malformed", "alert_sink_oserror"))
    rng = random.Random(args.seed ^ 0x5EED)
    lo, hi = max(1, args.ticks // 5), max(2, args.ticks * 4 // 5)
    if not 1 <= args.kills <= hi - lo:
        log(f"--kills {args.kills} does not fit the schedulable window "
            f"[{lo}, {hi}) of a {args.ticks}-tick run (1..{hi - lo})")
        return 2
    kill_ticks = sorted(rng.sample(range(lo, hi), args.kills))
    faults = sorted(
        base.faults + [Fault(kind="proc_exit", tick=t) for t in kill_ticks],
        key=lambda f: f.tick)
    spec = ChaosSpec(faults=faults, seed=args.seed)
    spec_path = os.path.join(workdir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec.to_dict(), f)
    log(f"supervised schedule: {len(base.faults)} in-process faults + "
        f"proc_exit at ticks {kill_ticks}, digest {spec.digest()}")

    alerts_path = os.path.join(workdir, "alerts.jsonl")
    child = [sys.executable, os.path.join(REPO, "scripts", "crash_soak.py"),
             "--child", "--workdir", workdir, "--seed", str(args.seed),
             "--ticks", str(args.ticks), "--streams", str(args.streams),
             "--group-size", str(args.group_size),
             "--cadence", str(args.cadence),
             "--checkpoint-every", str(args.checkpoint_every),
             "--backend", args.backend, "--threshold", str(-1e9),
             "--journal-fsync", "os", "--spec", spec_path,
             "--stats-out", os.path.join(workdir, "stats.jsonl")]
    sup = Supervisor(child, restart_budget=args.kills + 2,
                     backoff_base_s=0.05, backoff_max_s=1.0,
                     event_path=alerts_path, log=log)
    rc = sup.run(install_signals=False)

    failures: list[str] = []
    if rc != 0:
        failures.append(f"supervised run ended rc={rc} "
                        f"(deaths={sup.deaths})")
    from rtap_tpu.resilience.chaos import PROC_EXIT_CODE

    if sup.deaths != args.kills:
        failures.append(
            f"{sup.deaths} death(s) for {args.kills} scheduled proc_exit "
            "faults — each must fire exactly once across restarts")
    bad_rc = [r for r in sup.death_rcs if r != PROC_EXIT_CODE]
    if bad_rc:
        failures.append(
            f"death rc(s) {bad_rc} are not the injected proc_exit "
            f"(rc {PROC_EXIT_CODE}) — a real crash rode the schedule")
    total = 0
    stats_path = os.path.join(workdir, "stats.jsonl")
    if os.path.isfile(stats_path):
        with open(stats_path) as f:
            for line in f:
                s = json.loads(line)
                total = max(total, s["base"] + s["ran"])
    if total != args.ticks:
        failures.append(f"run completed {total} of {args.ticks} total "
                        "ticks across restarts")
    # one scanner for both soaks: crash_soak's parse_alert_stream owns
    # the event-vs-alert split and torn-fragment tolerance
    from scripts.crash_soak import parse_alert_stream

    parsed = parse_alert_stream(alerts_path)
    seen_ids = set(parsed["alerts"])
    dup = parsed["dup"]
    replay_events = sum(1 for e in parsed["events"]
                        if e.get("event") == "journal_replayed")
    if dup:
        failures.append(f"{len(dup)} duplicated alert_id(s) across "
                        f"restarts: {dup[:5]}")
    if args.kills and not replay_events:
        failures.append("no journal_replayed event despite kills — "
                        "recovery never ran")
    report = {
        "mode": "supervise",
        "seed": args.seed,
        "schedule_digest": spec.digest(),
        "proc_exit_ticks": kill_ticks,
        "deaths": sup.deaths,
        "ticks_completed": total,
        "alert_ids": len(seen_ids),
        "duplicated": len(dup),
        "journal_replay_events": replay_events,
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
    log(f"OK: {sup.deaths} proc_exit death(s), {total} ticks completed, "
        f"{len(seen_ids)} alert ids unique, {replay_events} journal "
        "replays")
    return 0


def run_topology_burst(args) -> int:
    """`--topology-burst`: a correlated multi-group value burst rides the
    seeded schedule; the verdict is ONE cluster-level incident, not N
    per-stream pages (ISSUE 9)."""
    import dataclasses

    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.correlate import IncidentCorrelator, TopologyMap
    from rtap_tpu.resilience import ChaosEngine, ChaosSpec, Fault
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    if args.streams < 12:
        log("--topology-burst floods nodes n1+n2 (stream indices 3..8) "
            "and needs healthy bystanders; use --streams >= 12")
        return 2
    # short probation so the default 120-tick run has a mature burst
    # window: burst at 3/4 of the run, likelihood ready at tick 60
    probation = 40 + 20
    burst_tick = args.ticks * 3 // 4
    burst_dur = 8
    if burst_tick <= probation + 5:
        log(f"burst tick {burst_tick} inside the likelihood probation "
            f"{probation} — raise --ticks (>= 96)")
        return 2
    ids = [f"n{i // 3}.m{i % 3}" for i in range(args.streams)]
    cfg = cluster_preset()
    cfg = dataclasses.replace(cfg, likelihood=dataclasses.replace(
        cfg.likelihood, learning_period=40, estimation_samples=20))
    reg = StreamGroupRegistry(cfg, group_size=args.group_size,
                              backend=args.backend, threshold=0.1,
                              debounce=2)
    for sid in ids:
        reg.add_stream(sid)
    reg.finalize()

    # the blast radius: every metric of nodes n1 and n2 — six streams
    # whose indices straddle a group boundary at the default group size
    burst_idx = tuple(range(3, 9))
    burst_nodes = sorted({ids[i].split(".")[0] for i in burst_idx})
    burst_groups = sorted({i // args.group_size for i in burst_idx})
    if len(burst_groups) < 2:
        log(f"burst indices {burst_idx} land in one group at "
            f"--group-size {args.group_size}; use a size that splits "
            "them (the point is a MULTI-group burst)")
        return 2
    base = ChaosSpec.generate(seed=args.seed, n_ticks=args.ticks,
                              rate=args.rate, kinds=("source_timeout",))
    burst = Fault(kind="topology_burst", tick=burst_tick,
                  duration=burst_dur, streams=burst_idx)
    spec = ChaosSpec(faults=sorted(base.faults + [burst],
                                   key=lambda f: f.tick), seed=args.seed)
    engine = ChaosEngine(spec)
    log(f"schedule: burst on {burst_nodes} (groups {burst_groups}) at "
        f"tick {burst_tick} + {len(base.faults)} background fault(s), "
        f"digest {spec.digest()}")

    correlator = IncidentCorrelator(TopologyMap.infer(), window_s=6,
                                    min_streams=4)

    def source(k: int):
        rng = np.random.Generator(np.random.Philox(key=(args.seed, k)))
        return (30 + 5 * rng.random(len(ids))).astype(np.float32), \
            1_700_000_000 + k

    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_topo_")
    os.makedirs(workdir, exist_ok=True)
    alerts_path = os.path.join(workdir, "alerts.jsonl")
    stats = live_loop(
        source, reg, n_ticks=args.ticks, cadence_s=args.cadence,
        alert_path=alerts_path, chaos=engine, correlator=correlator)

    failures: list[str] = []
    if stats["ticks"] != args.ticks:
        failures.append(
            f"loop stopped at tick {stats['ticks']} of {args.ticks}")
    if "topology_burst" not in {e["kind"] for e in engine.injected}:
        failures.append("the scheduled topology_burst never injected")
    # the incident contract is THE shared checker (one copy — a schema
    # change cannot silently de-fang one of the two topology soaks)
    from scripts.crash_soak import parse_alert_stream
    from scripts.workload_soak import check_single_incident

    parsed = parse_alert_stream(alerts_path)
    incs = check_single_incident(alerts_path, burst_nodes,
                                 correlator.min_streams, failures,
                                 "topology-burst", parsed=parsed)

    report = {
        "mode": "topology_burst",
        "seed": args.seed,
        "schedule_digest": spec.digest(),
        "burst_tick": burst_tick,
        "burst_nodes": burst_nodes,
        "burst_groups": burst_groups,
        "faults_injected": engine.injected,
        "alert_ids": len(set(parsed["alerts"])),
        "incidents": len(incs),
        "incident": incs[0] if len(incs) == 1 else None,
        "correlator": correlator.stats(),
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
    log(f"OK: 1 incident across groups {burst_groups} "
        f"({incs[0]['members']} members, {len(incs[0]['nodes'])} nodes) "
        f"from {len(set(parsed['alerts']))} per-stream alert(s)")
    return 0


def run_replication(args) -> int:
    """`--replication`: seeded wire faults against a live leader/standby
    pair; the verdict is standby state bit-identical to the leader's."""
    import threading
    import time

    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.resilience import (
        ChaosEngine,
        ChaosSpec,
        Lease,
        ReplicationSender,
        StandbyFollower,
        TickJournal,
    )
    from rtap_tpu.service.loop import _save_all, live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry
    from scripts.crash_soak import compare_states

    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_repl_")
    os.makedirs(workdir, exist_ok=True)

    def build_reg():
        reg = StreamGroupRegistry(cluster_preset(),
                                  group_size=args.group_size,
                                  backend=args.backend, threshold=-1e9,
                                  debounce=1)
        for i in range(args.streams):
            reg.add_stream(f"n{i // 3}.m{i % 3}")
        reg.finalize()
        return reg

    leader_reg, standby_reg = build_reg(), build_reg()
    spec = ChaosSpec.generate(
        seed=args.seed, n_ticks=args.ticks, rate=args.rate,
        kinds=("conn_drop", "stall_socket", "corrupt_bytes"))
    engine = ChaosEngine(spec)
    log(f"replication schedule: {len(spec.faults)} wire faults over "
        f"{args.ticks} ticks, digest {spec.digest()}")

    lease_path = os.path.join(workdir, "lease")
    # the pair must STAY a pair for this soak: the standby's lease view
    # uses an enormous timeout so it never promotes mid-run
    leader_lease = Lease(lease_path, "leader", timeout_s=30.0)
    standby_lease = Lease(lease_path, "standby", timeout_s=1e9)
    assert leader_lease.try_acquire()

    stop = threading.Event()
    standby_journal = TickJournal(os.path.join(workdir, "standby-journal"))
    follower = StandbyFollower(
        standby_reg, standby_journal, lease=standby_lease, port=0,
        alert_path=None, checkpoint_dir=os.path.join(workdir, "ck"),
        cadence_s=args.cadence, stop_event=stop)
    results: dict = {}

    def follow():
        results["follow"] = follower.run()

    t = threading.Thread(target=follow, daemon=True)
    t.start()
    deadline = time.monotonic() + 30.0
    while follower.address is None and time.monotonic() < deadline:
        time.sleep(0.01)
    if follower.address is None:
        log("FATAL: standby listener never came up")
        return 3

    leader_journal = TickJournal(os.path.join(workdir, "leader-journal"))
    sender = ReplicationSender(
        follower.address, leader_journal,
        checkpoint_dir=os.path.join(workdir, "ck"), chaos=engine).start()
    leader_journal.tee = sender.tee
    leader_journal.compact_floor = sender.compact_floor

    def source(k: int):
        rng = np.random.Generator(np.random.Philox(key=(args.seed, k)))
        return (30 + 5 * rng.random(
            len(leader_reg.dispatch_ids()))).astype(np.float32), \
            1_700_000_000 + k

    stats = live_loop(
        source, leader_reg, n_ticks=args.ticks, cadence_s=args.cadence,
        alert_path=os.path.join(workdir, "alerts.jsonl"),
        checkpoint_dir=os.path.join(workdir, "ck"),
        checkpoint_every=args.checkpoint_every,
        journal=leader_journal, lease=leader_lease)

    failures: list[str] = []
    # let the standby drain the tail (the wire is asynchronous)
    deadline = time.monotonic() + 60.0
    while follower.expected < args.ticks and time.monotonic() < deadline:
        time.sleep(0.02)
    if follower.expected < args.ticks:
        failures.append(
            f"standby applied only {follower.expected} of {args.ticks} "
            "ticks before the drain deadline")
    leader_journal.close()
    sender.close()
    stop.set()
    t.join(timeout=30.0)
    standby_journal.close()

    # the verdict: bit-identical model state, leader vs standby, via the
    # checkpoint comparison the crash soak already owns
    lck = os.path.join(workdir, "verify-leader")
    sck = os.path.join(workdir, "verify-standby")
    _save_all(leader_reg.groups, lck)
    _save_all(standby_reg.groups, sck)
    leaves = compare_states(lck, sck, failures)
    injected_kinds = {e["kind"] for e in engine.injected}
    scheduled_kinds = {f.kind for f in spec.faults}
    missing = sorted(scheduled_kinds - injected_kinds)
    if missing:
        failures.append(f"scheduled wire fault kind(s) never injected: "
                        f"{missing}")
    if stats["ticks"] != args.ticks:
        failures.append(f"leader ran {stats['ticks']} of {args.ticks}")

    report = {
        "mode": "replication",
        "seed": args.seed,
        "schedule_digest": spec.digest(),
        "faults_scheduled": len(spec.faults),
        "faults_injected": engine.injected,
        "standby": follower.stats(),
        "sender": sender.stats(),
        "state_leaves_compared": leaves,
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
    log(f"OK: {len(engine.injected)} wire fault(s) injected, standby "
        f"applied {follower.applied} ticks, {leaves} state leaves "
        "bit-identical")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0,
                    help="fault-schedule seed; same seed = same schedule")
    ap.add_argument("--streams", type=int, default=12)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--cadence", type=float, default=0.05)
    ap.add_argument("--rate", type=float, default=0.08,
                    help="per-tick fault probability in the generated "
                         "schedule")
    ap.add_argument("--backend", default="tpu")
    ap.add_argument("--checkpoint-every", type=int, default=5)
    ap.add_argument("--restore-after", type=int, default=6,
                    help="quarantine cooldown before checkpoint restore")
    ap.add_argument("--workdir", default=None,
                    help="alerts + checkpoints land here (default: a "
                         "fresh temp dir)")
    ap.add_argument("--out", default=None, help="report JSON path")
    ap.add_argument("--supervise", action="store_true",
                    help="out-of-process mode (ISSUE 5): add seeded "
                         "proc_exit kills and run the journaled serve "
                         "child under the Supervisor; verify restarts, "
                         "journal recovery, and zero duplicated alert ids")
    ap.add_argument("--kills", type=int, default=2,
                    help="proc_exit faults scheduled with --supervise")
    ap.add_argument("--replication", action="store_true",
                    help="leader/standby mode (ISSUE 8): seeded "
                         "conn_drop/stall_socket/corrupt_bytes faults on "
                         "the replication wire; verify the standby's "
                         "state stays bit-identical to the leader's")
    ap.add_argument("--topology-burst", action="store_true",
                    help="incident-correlation mode (ISSUE 9): inject a "
                         "correlated multi-group value burst with "
                         "correlation armed; verify exactly ONE cluster-"
                         "level incident pages, not N per-stream alerts")
    args = ap.parse_args()
    maybe_force_cpu()
    if args.backend == "tpu":
        require_device()  # no TPU and no explicit CPU choice -> fail here
    if sum((args.supervise, args.replication, args.topology_burst)) > 1:
        log("--supervise, --replication and --topology-burst are "
            "separate drills")
        return 2
    if args.topology_burst:
        return run_topology_burst(args)
    if args.replication:
        return run_replication(args)
    if args.supervise:
        return run_supervised(args)

    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.resilience import ChaosEngine, ChaosSpec
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    ids = [f"n{i // 3}.m{i % 3}" for i in range(args.streams)]
    reg = StreamGroupRegistry(cluster_preset(), group_size=args.group_size,
                              backend=args.backend)
    for sid in ids:
        reg.add_stream(sid)
    reg.finalize()
    n_groups = len(reg.groups)

    spec = ChaosSpec.generate(seed=args.seed, n_ticks=args.ticks,
                              n_groups=n_groups, rate=args.rate)
    digest = spec.digest()
    # reproducibility is a hard contract, not an aspiration: regenerate
    # and compare before trusting the run
    if ChaosSpec.generate(seed=args.seed, n_ticks=args.ticks,
                          n_groups=n_groups, rate=args.rate
                          ).digest() != digest:
        log("FATAL: schedule generation is not deterministic")
        return 3
    # group-targeted source_timeout faults resolve to that group's slice
    # of the source vector inside live_loop (ChaosEngine.set_group_streams
    # from the loop's routing) — one exporter's worth of streams times
    # out, the rest of the fleet's inputs stay untouched
    engine = ChaosEngine(spec)
    log(f"schedule: {len(spec.faults)} faults over {args.ticks} ticks, "
        f"digest {digest}")

    def source(k: int):
        rng = np.random.Generator(np.random.Philox(key=(args.seed, k)))
        return (30 + 5 * rng.random(len(ids))).astype(np.float32), \
            1_700_000_000 + k

    workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_soak_")
    os.makedirs(workdir, exist_ok=True)
    alerts_path = os.path.join(workdir, "alerts.jsonl")
    # black-box coverage (ISSUE 4): every chaos run flies with the span
    # recorder + flight recorder armed, and the verdict below asserts a
    # chaos-induced quarantine left a VALID postmortem bundle behind
    from rtap_tpu.obs import FlightRecorder, TraceRecorder, validate_bundle

    trace = TraceRecorder(capacity=32768)
    pm_dir = os.path.join(workdir, "postmortems")
    flight = FlightRecorder(
        trace=trace, n_ticks=min(args.ticks, 240), out_dir=pm_dir,
        info={"command": "chaos_soak", "seed": args.seed,
              "schedule_digest": digest, "streams": args.streams,
              "group_size": args.group_size})
    stats = live_loop(
        source, reg, n_ticks=args.ticks, cadence_s=args.cadence,
        alert_path=alerts_path,
        checkpoint_dir=os.path.join(workdir, "ck"),
        checkpoint_every=args.checkpoint_every,
        quarantine_restore_after=args.restore_after,
        chaos=engine, trace=trace, flight=flight)

    with open(alerts_path) as f:
        events = [json.loads(line) for line in f
                  if line.startswith('{"event"')]
    failures: list[str] = []
    if stats["ticks"] != args.ticks:
        failures.append(
            f"loop stopped at tick {stats['ticks']} of {args.ticks}")
    # intervals come from the loop's own quarantine log, NOT the alert
    # stream: the sink may have been the faulted component, and a dropped
    # event line must not fail an otherwise-correct run
    intervals = _unquarantined_intervals(
        stats.get("quarantine_log", []), n_groups, stats["ticks"])
    expected = [sum(b - a for a, b in intervals[g]) * reg.groups[g].n_live
                for g in range(n_groups)]
    got = stats["scored_by_group"]
    for g in range(n_groups):
        if got[g] != expected[g]:
            failures.append(
                f"group{g}: scored {got[g]} but its unquarantined "
                f"intervals {intervals[g]} require {expected[g]} — streams "
                "silently stopped being scored while unquarantined")
    if sum(got) != stats["scored"]:
        failures.append(
            f"per-group counts sum to {sum(got)} != scored "
            f"{stats['scored']}")

    # ---- postmortem-bundle verdict: a chaos-injected quarantine must
    # leave a loadable black box behind (trace spans + event lines > 0)
    quarantines = [e for e in stats.get("quarantine_log", [])
                   if e["event"] == "group_quarantined"]
    bundle_dirs = sorted(
        os.path.join(pm_dir, d) for d in os.listdir(pm_dir)
        if not d.startswith(".tmp")) if os.path.isdir(pm_dir) else []
    verdicts = [validate_bundle(b) for b in bundle_dirs]
    if quarantines and not bundle_dirs:
        failures.append(
            f"{len(quarantines)} quarantine(s) occurred but no postmortem "
            "bundle was dumped")
    for b, v in zip(bundle_dirs, verdicts):
        if not v["ok"]:
            failures.append(f"invalid postmortem bundle {b}: {v['problems']}")
        elif v["events"] == 0:
            failures.append(f"postmortem bundle {b} captured zero events")
    pm_report = {
        "dir": pm_dir,
        "bundles": [os.path.basename(b) for b in bundle_dirs],
        "valid": sum(1 for v in verdicts if v["ok"]),
        "spans": sum(v["spans"] for v in verdicts),
        "instants": sum(v["instants"] for v in verdicts),
        "events": sum(v["events"] for v in verdicts),
        "dumps_skipped": stats.get("postmortem", {}).get("dumps_skipped", 0),
        "trace_records": trace.total,
        "trace_dropped": trace.dropped,
    }

    report = {
        "seed": args.seed,
        "schedule_digest": digest,
        "faults_scheduled": len(spec.faults),
        "faults_injected": engine.injected,
        "events": sorted({e["event"] for e in events}),
        "intervals": {f"group{g}": intervals[g] for g in range(n_groups)},
        "expected_by_group": expected,
        "postmortem": pm_report,
        "stats": stats,
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
    log(f"OK: {stats['scored']} scored, "
        f"{len(engine.injected)} faults injected, "
        f"{len(quarantines)} quarantines, "
        f"{pm_report['valid']} valid postmortem bundle(s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
