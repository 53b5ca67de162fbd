"""Workload soak: seeded cascading fault -> exactly ONE incident, kill-9 safe.

ISSUE 9 acceptance surface (cpu — no silicon needed). A deterministic
multi-service cluster (data/synthetic.generate_topology_workload) takes
one seeded cascading burst: a chosen service's nodes spike one after
another (cascade lag ticks apart) across all their metrics while every
other service stays healthy. The serve child flies with the full
durability stack (journal + periodic checkpoints) AND topology-aware
incident correlation armed. The run FAILS (exit 5) unless:

- the fault-free reference run emits EXACTLY ONE cluster-level incident,
  covering >= --min-streams member streams, whose blast-radius node set
  is exactly the faulted service's nodes, and every member alert_id
  references an alert actually on the stream;
- the crash run (a seeded killer SIGKILLs the supervised child K times,
  at least once DURING the incident's open window — the hard case: the
  correlator's state dies mid-fold and must rebuild from the sink tail)
  produces an incident stream IDENTICAL to the reference's (same
  incident ids, same member sets, same blast radii — exactly-once
  across journal replay);
- the alert stream is exactly-once (crash_soak's machinery) and the
  final model state is bit-identical to the reference run's.

In-tree smoke: tests/integration/test_workloads_serve.py runs K=1 at a
tiny config. Usage:

    python scripts/workload_soak.py --seed 0 --kills 2 [--ticks 220]
        [--services 3] [--nodes-per-service 3] [--cadence 0.02]
        [--checkpoint-every 15] [--out reports/workload_soak.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import maybe_force_cpu, require_device  # noqa: E402

VERIFY_FAILED_EXIT = 5
INFRA_FAILED_EXIT = 3

#: likelihood shape every soak child shares: short probation so a
#: few-hundred-tick run has a mature post-probation burst window
SOAK_LEARNING_PERIOD = 60
SOAK_ESTIMATION = 30


def log(msg: str) -> None:
    print(f"[workload] {msg}", file=sys.stderr, flush=True)


def build_workload(args):
    from rtap_tpu.data.synthetic import (
        SyntheticStreamConfig,
        generate_topology_workload,
    )

    scfg = SyntheticStreamConfig(length=args.ticks, n_anomalies=0,
                                 noise_phi=0.9, noise_scale=0.3)
    return generate_topology_workload(
        n_services=args.services,
        nodes_per_service=args.nodes_per_service,
        cfg=scfg, seed=args.seed, burst_at_frac=args.burst_at_frac,
        cascade_lag=args.cascade_lag, burst_dur=args.burst_dur,
        burst_magnitude=args.burst_magnitude)


# ---------------------------------------------------------------- child
def run_child(args) -> int:
    """One serve-process lifetime over the seeded workload feed, with
    journal + checkpoints + incident correlation armed (crash_soak's
    child shape — killed children leave their trail behind)."""
    maybe_force_cpu()
    if args.backend == "tpu":
        require_device()  # no TPU and no explicit CPU choice -> fail here

    import dataclasses

    import numpy as np

    from rtap_tpu.config import cluster_preset, composite_preset
    from rtap_tpu.correlate import IncidentCorrelator, TopologyMap
    from rtap_tpu.resilience import TickJournal
    from rtap_tpu.service.checkpoint import peek_resume_ticks
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    w = args.workdir
    os.makedirs(w, exist_ok=True)
    journal = TickJournal(os.path.join(w, "journal"))
    ckdir = os.path.join(w, "ck")
    base = max(journal.next_tick, peek_resume_ticks(ckdir))
    n_eff = max(0, args.ticks - base)

    wl = build_workload(args)
    ids = [s.stream_id for s in wl.streams]
    values = np.stack([s.values for s in wl.streams], axis=1)  # [T, N]
    ts = wl.streams[0].timestamps

    if args.preset == "composite":
        # the silicon shape (hw_session r12_workloads): the same seeded
        # cascade scored through the composite multi-field encoder —
        # value + delta both carry the wire value (the encoder
        # differentiates internally), the event-class column is quiet
        values = np.stack(
            [values, values, np.zeros_like(values)], axis=2)  # [T, N, 3]
        base_cfg = composite_preset()
    else:
        base_cfg = cluster_preset()
    cfg = dataclasses.replace(base_cfg, likelihood=dataclasses.replace(
        base_cfg.likelihood, learning_period=SOAK_LEARNING_PERIOD,
        estimation_samples=SOAK_ESTIMATION))
    reg = StreamGroupRegistry(cfg, group_size=args.group_size,
                              backend=args.backend,
                              threshold=args.threshold, debounce=2)
    for sid in ids:
        reg.add_stream(sid)
    reg.finalize()

    correlator = IncidentCorrelator(
        TopologyMap.from_spec(wl.spec),
        window_s=args.correlate_window, min_streams=args.min_streams)

    def source(k: int):
        g = base + k  # the feed depends only on the GLOBAL tick
        return values[g], int(ts[g])

    stats = live_loop(
        source, reg, n_ticks=n_eff, cadence_s=args.cadence,
        alert_path=os.path.join(w, "alerts.jsonl"),
        checkpoint_dir=ckdir, checkpoint_every=args.checkpoint_every,
        journal=journal, correlator=correlator)
    journal.close()
    line = {"base": base, "ran": stats["ticks"], "alerts": stats["alerts"],
            "incidents": stats.get("incidents", {})}
    with open(os.path.join(w, "stats.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line))
    return 0


# --------------------------------------------------------------- parent
def child_cmd(args, workdir: str) -> list[str]:
    return [sys.executable, os.path.abspath(__file__), "--child",
            "--workdir", workdir, "--seed", str(args.seed),
            "--ticks", str(args.ticks),
            "--services", str(args.services),
            "--nodes-per-service", str(args.nodes_per_service),
            "--group-size", str(args.group_size),
            "--cadence", str(args.cadence),
            "--checkpoint-every", str(args.checkpoint_every),
            "--backend", args.backend, "--preset", args.preset,
            "--threshold", str(args.threshold),
            "--correlate-window", str(args.correlate_window),
            "--min-streams", str(args.min_streams),
            "--burst-at-frac", str(args.burst_at_frac),
            "--cascade-lag", str(args.cascade_lag),
            "--burst-dur", str(args.burst_dur),
            "--burst-magnitude", str(args.burst_magnitude)]


def incident_records(path: str) -> list[dict]:
    from rtap_tpu.service.alerts import iter_alert_records

    return [rec for kind, rec in iter_alert_records(path)
            if kind == "event" and rec.get("event") == "incident"]


def check_single_incident(alerts_path: str, expected_nodes, min_streams: int,
                          failures: list[str], label: str,
                          parsed: dict | None = None) -> list[dict]:
    """THE shared topology-soak incident contract (this soak and
    chaos_soak --topology-burst verify the same promise — one checker,
    so a schema change cannot silently de-fang one of them): exactly ONE
    incident on the stream, blast radius == the expected node set, >=
    ``min_streams`` distinct member streams, and every member alert_id
    referencing an alert line actually on the stream
    (docs/WORKLOADS.md incident schema). ``parsed``: a pre-computed
    parse_alert_stream result to reuse instead of re-walking the file."""
    from scripts.crash_soak import parse_alert_stream

    incs = incident_records(alerts_path)
    if len(incs) != 1:
        failures.append(f"{label}: {len(incs)} incident(s) emitted, "
                        f"expected exactly 1 for the seeded burst")
        return incs
    inc = incs[0]
    if len(inc["streams"]) < min_streams:
        failures.append(f"{label}: incident groups {len(inc['streams'])} "
                        f"distinct stream(s), below min_streams "
                        f"{min_streams}")
    if sorted(inc["nodes"]) != sorted(expected_nodes):
        failures.append(f"{label}: blast radius {inc['nodes']} != faulted "
                        f"nodes {sorted(expected_nodes)}")
    ids_on_stream = set((parsed if parsed is not None
                         else parse_alert_stream(alerts_path))["alerts"])
    missing = [a for a in inc["alert_ids"] if a not in ids_on_stream]
    if missing:
        failures.append(f"{label}: {len(missing)} incident member "
                        f"alert_id(s) not on the alert stream: "
                        f"{missing[:5]}")
    return incs


def verify_incident_stream(args, wl, ref_alerts: str, failures: list[str],
                           label: str) -> list[dict]:
    """This soak's per-run checks: the shared contract against the
    seeded cascade's faulted nodes."""
    return check_single_incident(ref_alerts, wl.burst_nodes,
                                 args.min_streams, failures, label)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kills", type=int, default=2)
    ap.add_argument("--ticks", type=int, default=220)
    ap.add_argument("--services", type=int, default=3)
    ap.add_argument("--nodes-per-service", type=int, default=3)
    ap.add_argument("--group-size", type=int, default=6)
    ap.add_argument("--cadence", type=float, default=0.02)
    ap.add_argument("--checkpoint-every", type=int, default=15)
    ap.add_argument("--backend", default="cpu")
    ap.add_argument("--preset", choices=("cluster", "composite"),
                    default="cluster",
                    help="model family for the soak children: cluster "
                         "(scalar RDSE — the acceptance default) or "
                         "composite (the ISSUE 9 multi-field encoder; "
                         "the hw_session r12_workloads silicon shape)")
    ap.add_argument("--threshold", type=float, default=0.1,
                    help="log-likelihood alert threshold: with the soak's "
                         "short probation the scalar burst peaks ~0.2 "
                         "while the healthy baseline sits ~0.02. The "
                         "composite preset's contrast profile is flatter "
                         "(burst ~0.07-0.09 vs healthy ~0.02 — the fused "
                         "SDR spreads novelty over three fields): pass "
                         "--threshold 0.04 with --preset composite")
    ap.add_argument("--correlate-window", type=int, default=10)
    ap.add_argument("--min-streams", type=int, default=3)
    ap.add_argument("--burst-at-frac", type=float, default=0.72)
    ap.add_argument("--cascade-lag", type=int, default=2)
    ap.add_argument("--burst-dur", type=int, default=10)
    ap.add_argument("--burst-magnitude", type=float, default=12.0)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None, help="report JSON path")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    maybe_force_cpu()
    if args.child:
        return run_child(args)

    import random
    import subprocess

    from rtap_tpu.resilience import Supervisor, last_journal_tick
    from scripts.crash_soak import compare_states, parse_alert_stream

    wl = build_workload(args)
    onset0 = min(wl.burst_onsets.values())
    probation = SOAK_LEARNING_PERIOD + SOAK_ESTIMATION
    if onset0 <= probation + 10:
        log(f"FATAL: burst onset {onset0} inside the likelihood probation "
            f"{probation} — lengthen --ticks or raise --burst-at-frac")
        return 2
    workdir = args.workdir or tempfile.mkdtemp(prefix="workload_soak_")
    ref_dir = os.path.join(workdir, "ref")
    crash_dir = os.path.join(workdir, "crash")
    os.makedirs(ref_dir, exist_ok=True)
    os.makedirs(crash_dir, exist_ok=True)
    failures: list[str] = []
    t0 = time.monotonic()

    # 1. fault-free reference
    log(f"reference run: {args.ticks} ticks, {len(wl.streams)} streams, "
        f"burst service {wl.burst_service} at tick {onset0}")
    rc = subprocess.run(child_cmd(args, ref_dir)).returncode
    if rc != 0:
        log(f"FATAL: reference run failed rc={rc}")
        return INFRA_FAILED_EXIT
    ref_incs = verify_incident_stream(
        args, wl, os.path.join(ref_dir, "alerts.jsonl"), failures,
        "reference")

    # 2. the crash run: seeded kills, one pinned INSIDE the incident's
    # open window (the correlator state dies mid-fold)
    rng = random.Random(args.seed ^ 0xB1A57)
    lo = max(args.checkpoint_every + 2, args.ticks // 5)
    in_window = onset0 + args.burst_dur // 2
    pool = [t for t in range(lo, args.ticks * 4 // 5)
            if abs(t - in_window) > 3]
    targets = sorted([in_window] + rng.sample(pool, max(0, args.kills - 1)))
    log(f"crash run: SIGKILL at journal ticks ~{targets}")
    sup = Supervisor(child_cmd(args, crash_dir),
                     restart_budget=args.kills + 2,
                     backoff_base_s=0.05, backoff_max_s=1.0, log=log)
    observed: list[int] = []
    killer = threading.Thread(
        target=_killer, args=(sup, os.path.join(crash_dir, "journal"),
                              targets, observed, failures), daemon=True)
    killer.start()
    rc = sup.run(install_signals=False)
    killer.join(timeout=120.0)
    if rc != 0:
        failures.append(f"crash run ended rc={rc} (deaths={sup.deaths})")
    if sup.deaths != args.kills:
        failures.append(f"supervisor saw {sup.deaths} death(s), "
                        f"scheduled {args.kills}")
    bad_sigs = [s for s in sup.kill_signals if s != 9]
    if bad_sigs:
        failures.append(f"non-SIGKILL deaths observed: {bad_sigs}")

    # 3. verdicts
    crash_incs = verify_incident_stream(
        args, wl, os.path.join(crash_dir, "alerts.jsonl"), failures,
        "crash-run")
    # order-independent, content-exact comparison: the crash run's
    # incident records must be EXACTLY the reference's (a resume may
    # reorder the event line relative to later alerts, never change it)
    ref_sorted = sorted(json.dumps(i, sort_keys=True) for i in ref_incs)
    got_sorted = sorted(json.dumps(i, sort_keys=True) for i in crash_incs)
    if ref_sorted != got_sorted:
        failures.append("incident stream differs across kill-9 resume "
                        "(content compare by sorted record)")

    ref_alerts = parse_alert_stream(os.path.join(ref_dir, "alerts.jsonl"))
    got_alerts = parse_alert_stream(os.path.join(crash_dir, "alerts.jsonl"))
    if got_alerts["dup"]:
        failures.append(f"{len(got_alerts['dup'])} DUPLICATED alert_id(s)")
    lost = sorted(set(ref_alerts["alerts"]) - set(got_alerts["alerts"]))
    extra = sorted(set(got_alerts["alerts"]) - set(ref_alerts["alerts"]))
    if lost:
        failures.append(f"{len(lost)} LOST alert_id(s): {lost[:5]}")
    if extra:
        failures.append(f"{len(extra)} EXTRA alert_id(s): {extra[:5]}")
    if not ref_alerts["alerts"]:
        failures.append("reference run emitted zero alerts — the soak "
                        "proves nothing (lower --threshold)")
    leaves = compare_states(os.path.join(ref_dir, "ck"),
                            os.path.join(crash_dir, "ck"), failures)

    report = {
        "seed": args.seed,
        "streams": len(wl.streams),
        "burst_service": wl.burst_service,
        "burst_nodes": wl.burst_nodes,
        "burst_onset_tick": onset0,
        "kill_targets": targets,
        "kills_observed_at": observed,
        "deaths": sup.deaths,
        "alert_ids": len(ref_alerts["alerts"]),
        "incidents_reference": len(ref_incs),
        "incidents_crash_run": len(crash_incs),
        "incident": ref_incs[0] if len(ref_incs) == 1 else None,
        "state_leaves_compared": leaves,
        "wall_s": round(time.monotonic() - t0, 1),
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
    log(f"OK: 1 incident ({report['incident']['members']} members, "
        f"{len(report['incident']['nodes'])} nodes), "
        f"{len(ref_alerts['alerts'])} alert ids exactly-once, "
        f"{leaves} state leaves bit-identical across {sup.deaths} kill(s)")
    return 0


def _killer(sup, journal_dir: str, targets: list[int], observed: list,
            failures: list[str]) -> None:
    from scripts.crash_soak import _killer as crash_killer

    crash_killer(sup, journal_dir, targets, observed, failures)


if __name__ == "__main__":
    raise SystemExit(main())
