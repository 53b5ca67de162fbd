"""Operator CLI: ``python -m rtap_tpu <command>``.

The reference is an application, not just a library — its operators launch
the collector/service loop, replay corpora, and evaluate detection from the
command line (SURVEY.md L4/L5, §3.3-3.5). This is that surface, thin glue
over the library:

    serve    live scoring loop at a fixed cadence, fed by a TCP JSONL push
             listener or an HTTP poll endpoint (service/sources.py, C18)
    replay   synthetic cluster replay through stream groups at full speed,
             JSONL alerts + throughput/occupancy stats (service/loop.py)
    eval     fault-injection evaluation -> JSON report (eval/fault_eval.py)
    report   matplotlib overlays from a replay/eval (scripts/report.py)

``scaling``/``profile`` remain scripts (scripts/) and the benchmark its own
package (``python -m benchmark.run``) since they are driver/measurement
surfaces, not operator ones.

Every command honors ``RTAP_FORCE_CPU=1`` (an explicit CPU run; without it
or ``JAX_PLATFORMS=cpu``, ``--backend tpu`` refuses to start where JAX finds
no TPU). The kernels take no setting: the TM step's form follows the model's
shape (docs/KERNELS.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from rtap_tpu.utils.platform import maybe_force_cpu


def _apply_cadence(cfg, args: argparse.Namespace):
    """ModelConfig.learn_every from the operator flag (SCALING.md
    "Learning-cadence operating curve"). Delegates to
    ModelConfig.with_learn_every — the shared policy — so an invalid k
    (0, negative) fails loudly instead of silently running full-rate."""
    return cfg.with_learn_every(getattr(args, "learn_every", 1),
                                full_until=getattr(args, "learn_full_until",
                                                   None),
                                burst=getattr(args, "learn_burst", 1))


def _predict_horizon(args: argparse.Namespace) -> int:
    """The predictive horizon a command's flags ask for: --predict-horizon,
    8 where --predict leaves it unset, 0 without --predict. The horizon
    sizes device state (the `pred_ring` leaf), so a fleet is warmed, saved
    and served with one number."""
    if not getattr(args, "predict", False):
        return 0
    k = getattr(args, "predict_horizon", None)
    return 8 if k is None else k


def _sized_cluster(args: argparse.Namespace):
    """cluster_preset, optionally width-scaled (--columns: SCALING.md model-
    width study — per-workload deployment choice; validation lives in
    scaled_cluster_preset, which rejects degenerate geometries loudly)."""
    from rtap_tpu.config import cluster_preset, scaled_cluster_preset

    cols = getattr(args, "columns", None)
    return cluster_preset() if cols is None else scaled_cluster_preset(cols)


def _cmd_serve(args: argparse.Namespace) -> int:
    # control plane (ISSUE 20, docs/RESILIENCE.md "Control plane"): owns
    # one fencing lease per shard + membership + the shard map, epochs
    # journaled write-ahead. Started before any service import so a
    # --control-only process never touches the accelerator stack.
    control_plane = None
    if args.control_listen is not None:
        from rtap_tpu.fleet.control import ControlPlane

        try:
            control_plane = ControlPlane(
                args.control_journal, port=args.control_listen,
                lease_timeout_s=args.lease_timeout).start()
        except (OSError, ValueError) as e:
            print(f"serve: control plane failed to start: {e}",
                  file=sys.stderr)
            return 2
        chost, cport = control_plane.address
        print(f"serve: control plane on {chost}:{cport} (journal "
              f"{args.control_journal}, {control_plane.recovered_shards} "
              "shard lease(s) recovered)", file=sys.stderr)
        if args.control_only:
            import signal
            import threading

            cstop = threading.Event()
            for _sig in (signal.SIGTERM, signal.SIGINT):
                signal.signal(_sig, lambda *_: cstop.set())
            cstop.wait()
            stats = control_plane.stats()
            control_plane.close()
            print(json.dumps({"control": stats, "stopped": True}))
            return 0

    from rtap_tpu.config import nab_preset
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry
    from rtap_tpu.service.shardpath import shard_scoped_path
    from rtap_tpu.service.sources import HttpPollSource, TcpJsonlSource

    # Shard-scope every operator resource path up front (ISSUE 15, the
    # shard-resource gate): one serve process = one mesh shard, and its
    # journal dir, checkpoint claims, lease file, and alert sink (plus
    # the .corr/.epoch sidecars derived from it downstream) must be
    # distinct per shard. --shard is the index ROADMAP-1's mesh launcher
    # (and the control-plane shard map) lands here; the single-shard
    # default is shard 0, where shard_scoped_path returns every path
    # byte-identical.
    serve_shard = int(getattr(args, "shard", 0) or 0)
    for _attr in ("journal_dir", "checkpoint_dir", "lease_file", "alerts"):
        if getattr(args, _attr, None):
            setattr(args, _attr,
                    shard_scoped_path(getattr(args, _attr), serve_shard))

    if args.streams.startswith("@"):
        # @file form: one stream id per line — a 16k-stream fleet's comma
        # list exceeds the kernel's single-argv limit (MAX_ARG_STRLEN,
        # observed at the live_soak_16k harvest step)
        try:
            with open(args.streams[1:]) as f:
                ids = [s.strip() for s in f if s.strip()]
        except OSError as e:
            print(f"serve: cannot read stream-id file {args.streams[1:]}: {e}",
                  file=sys.stderr)
            return 2
    else:
        ids = [s.strip() for s in args.streams.split(",") if s.strip()]
    if not ids:
        print("serve: --streams must name at least one stream id", file=sys.stderr)
        return 2
    if args.group_size < 1:
        print("serve: --group-size must be >= 1", file=sys.stderr)
        return 2
    # resilience wiring (docs/RESILIENCE.md): scripted fault injection and
    # the load-shedding ladder are operator opt-ins; quarantine itself is
    # always on (a faulted group must never take down the fleet). Parsed
    # BEFORE any source/registry construction: a bad spec is a usage
    # error, not a half-started serve with a listener to clean up.
    chaos = None
    if args.chaos_spec:
        from rtap_tpu.resilience import ChaosEngine, ChaosSpec

        try:
            chaos = ChaosEngine(ChaosSpec.from_file(args.chaos_spec))
        except (OSError, ValueError, KeyError, TypeError) as e:
            print(f"serve: bad --chaos-spec {args.chaos_spec}: {e}",
                  file=sys.stderr)
            return 2
        print(f"serve: chaos spec loaded ({len(chaos.spec.faults)} faults, "
              f"digest {chaos.spec.digest()})", file=sys.stderr)
    # topology-aware incident correlation (ISSUE 9, rtap_tpu/correlate/,
    # docs/WORKLOADS.md): parsed before any source/registry construction
    # — a bad spec is a usage error, not a half-started serve
    correlator = None
    if args.topology:
        from rtap_tpu.correlate import IncidentCorrelator, TopologyMap

        try:
            topo = TopologyMap.infer() if args.topology == "infer" \
                else TopologyMap.from_spec(args.topology)
            # only user-set knobs become kwargs — the class defaults
            # (window 30s, min 3 streams) have ONE owner
            knobs = {k: v for k, v in (
                ("window_s", args.correlate_window),
                ("min_streams", args.correlate_min_streams))
                if v is not None}
            correlator = IncidentCorrelator(topo, **knobs)
        except (OSError, ValueError, KeyError, TypeError) as e:
            print(f"serve: bad --topology {args.topology}: {e}",
                  file=sys.stderr)
            return 2
        print(f"serve: incident correlation armed ({'inferred' if args.topology == 'infer' else args.topology}; "
              f"window {correlator.window_s}s, min {correlator.min_streams} "
              "streams)", file=sys.stderr)
    # detection-latency observability + SLOs (ISSUE 11, obs/latency.py,
    # obs/slo.py, docs/SLO.md): specs parse BEFORE any source/registry
    # construction — a malformed --slo is a usage error, not a
    # half-started serve with a listener to clean up
    slo_specs = []
    if args.slo:
        from rtap_tpu.obs import parse_slo

        try:
            slo_specs = [parse_slo(s) for s in args.slo]
        except ValueError as e:
            print(f"serve: bad --slo: {e}", file=sys.stderr)
            return 2
    latency = None
    slo_tracker = None
    if args.latency:
        from rtap_tpu.obs import LatencyTracker

        try:
            latency = LatencyTracker(
                window_ticks=args.latency_window
                if args.latency_window is not None else 120,
                cadence_s=args.cadence)
        except ValueError as e:
            print(f"serve: bad --latency-window: {e}", file=sys.stderr)
            return 2
        print("serve: detection-latency tracking armed (window "
              f"{latency.window_ticks} ticks; GET /latency with "
              "--obs-port)", file=sys.stderr)
    if slo_specs:
        from rtap_tpu.obs import SloTracker

        try:
            slo_tracker = SloTracker(
                slo_specs, cadence_s=args.cadence,
                fast_window=args.slo_fast_window
                if args.slo_fast_window is not None else 60,
                slow_window=args.slo_slow_window
                if args.slo_slow_window is not None else 600,
                quantile_source=latency.quantile)
        except ValueError as e:
            print(f"serve: bad --slo/--slo-*-window: {e}",
                  file=sys.stderr)
            return 2
        print("serve: SLOs armed: "
              + ", ".join(s.label() for s in slo_specs)
              + f" (burn windows {slo_tracker.fast_window}/"
              f"{slo_tracker.slow_window} ticks)", file=sys.stderr)
    degradation = None
    if args.degrade:
        from rtap_tpu.resilience import DegradationController

        try:
            degradation = DegradationController(
                degrade_after=args.degrade_after,
                recover_after=args.degrade_recover_after)
        except ValueError as e:
            print(f"serve: bad --degrade parameters: {e}", file=sys.stderr)
            return 2
    # durability (docs/RESILIENCE.md, ISSUE 5): the per-tick write-ahead
    # journal. Constructing it performs recovery (torn tails truncated,
    # rows loaded for replay); with a journal, --ticks is the run's TOTAL
    # tick budget across restarts — a resumed serve catches up through
    # the journal and then runs only the remainder.
    journal = None
    n_ticks_eff = args.ticks
    if args.journal_dir:
        from rtap_tpu.resilience.journal import TickJournal, parse_fsync

        try:
            fsync_policy, fsync_every = parse_fsync(args.journal_fsync)
            journal = TickJournal(
                args.journal_dir,
                segment_bytes=args.journal_segment_bytes,
                max_segments=args.journal_max_segments,
                fsync=fsync_policy, fsync_every=fsync_every)
        except (OSError, ValueError) as e:
            print(f"serve: bad --journal-dir/--journal-fsync: {e}",
                  file=sys.stderr)
            return 2
        base = journal.next_tick
        if args.checkpoint_dir:
            from rtap_tpu.service.checkpoint import peek_resume_ticks

            base = max(base, peek_resume_ticks(args.checkpoint_dir))
        n_ticks_eff = max(0, args.ticks - base)
        if base:
            print(f"serve: resuming at tick {base} "
                  f"({len(journal.recovered_ticks)} journaled rows "
                  f"recovered; --ticks {args.ticks} is the total budget "
                  f"-> {n_ticks_eff} new ticks)", file=sys.stderr)
            if chaos is not None:
                # under a journal the chaos schedule is GLOBAL-tick
                # -indexed: a restarted serve shifts it onto its local
                # clock and fired faults (in particular the proc_exit
                # that killed the previous incarnation) drop out instead
                # of re-firing every restart
                from rtap_tpu.resilience import ChaosEngine as _CE

                chaos = _CE(chaos.spec.shifted(base))
                print(f"serve: chaos schedule shifted to resume base "
                      f"{base} ({len(chaos.spec.faults)} faults remain)",
                      file=sys.stderr)
        if journal.truncations or journal.dropped_segments:
            print(f"serve: journal tail truncated on recovery "
                  f"({journal.truncations} truncation(s), "
                  f"{journal.truncated_bytes} bytes, "
                  f"{journal.dropped_segments} dropped segment(s)) — "
                  "continuing from the last valid record", file=sys.stderr)
    # hot-standby replication + leadership lease (ISSUE 8,
    # docs/RESILIENCE.md failover runbook). The lease is constructed
    # here; a LEADER acquires it now (refusing to start split-brained),
    # a STANDBY only watches it until promotion.
    lease = None
    if args.lease_file:
        from rtap_tpu.resilience.replicate import Lease

        lease = Lease(args.lease_file,
                      owner=f"{os.uname().nodename}:{os.getpid()}",
                      timeout_s=args.lease_timeout)
        if not args.standby:
            if not lease.try_acquire():
                print(f"serve: lease {args.lease_file} is held by "
                      f"{lease.holder()!r} and fresh — refusing to serve "
                      "split-brained (start this process with --standby, "
                      "or wait out the lease timeout)", file=sys.stderr)
                return 2
            # liveness = process alive, not tick-loop fast: the
            # heartbeat keeps the lease fresh through multi-second
            # synchronous work (checkpoint rounds)
            lease.start_heartbeat()
    elif args.control_join:
        # same FencingLease surface, control-plane backend (ISSUE 20):
        # the loop, alert fence, follower and heartbeat cannot tell the
        # two apart — only the acquire/degrade semantics differ
        from rtap_tpu.fleet.control import ControlLease, parse_control_addr

        lease = ControlLease(
            parse_control_addr(args.control_join),
            owner=f"{os.uname().nodename}:{os.getpid()}",
            shard=serve_shard, timeout_s=args.lease_timeout,
            degraded_grace_s=args.control_grace)
        lease.hello("standby" if args.standby else "leader")
        if not args.standby:
            if not lease.try_acquire():
                print(f"serve: control plane {args.control_join} refused "
                      f"the shard {serve_shard} lease (held by "
                      f"{lease.holder()!r}, in its restart grace, or "
                      "unreachable) — start with --standby, or wait out "
                      "the lease timeout", file=sys.stderr)
                return 2
            lease.start_heartbeat()
    # (--columns + non-cluster presets rejected in main() before backend init)
    if args.preset == "nab":
        cfg = nab_preset()
    elif args.preset == "composite":
        from rtap_tpu.config import composite_preset

        cfg = composite_preset()
    elif args.preset == "categorical":
        from rtap_tpu.config import categorical_preset

        cfg = categorical_preset()
    elif args.preset == "node":
        from rtap_tpu.config import node_preset

        # one model a node over --fields metrics (cpu, mem, net): a wire
        # record is {"id", "values": [..F..], "ts"}, docs/INGEST.md
        cfg = node_preset(args.fields)
    else:
        cfg = _sized_cluster(args)
    cfg = _apply_cadence(cfg, args)
    # many groups per chip is the at-scale serving shape (throughput peaks
    # at small G — SCALING.md); capping at len(ids) keeps small serves in
    # one exactly-sized group with no pad slots
    gsize = min(args.group_size, len(ids))
    # --auto-register without reserved capacity can only claim group-size
    # rounding pads; make the elastic intent explicit by default
    reserve = args.reserve if args.reserve is not None \
        else (gsize if args.auto_register else 0)
    # predictive horizon (ISSUE 16): a non-zero k makes every group carry
    # the pred_* ring leaves and the fused reducer from tick 0 — the
    # horizon is structural (it sizes device state), so it is fixed at
    # registry construction, not toggled later
    predict_k = _predict_horizon(args)
    grp = StreamGroupRegistry(cfg, group_size=gsize,
                              backend=args.backend, threshold=args.threshold,
                              debounce=args.debounce,
                              stagger_learn=args.stagger_learn,
                              health=args.health,
                              predict=predict_k)
    for sid in ids:
        grp.add_stream(sid)
    grp.finalize(reserve=reserve)
    # orderly shutdown: SIGTERM/SIGINT finish the current tick (or end a
    # standby's follow loop), save final state, and still print stats —
    # installed BEFORE the standby block so a follow loop is stoppable
    import signal
    import threading

    stop = threading.Event()
    prev = {}

    def _on_signal(*_):
        stop.set()
        # restore the previous handlers so a SECOND signal force-exits —
        # a tick wedged on the device must not make the process
        # unkillable except by SIGKILL
        for s, h in prev.items():
            signal.signal(s, h)

    for sig in (signal.SIGTERM, signal.SIGINT):
        prev[sig] = signal.signal(sig, _on_signal)
    if lease is not None and hasattr(lease, "on_drain"):
        # a control-plane drain mark becomes an orderly between-tick
        # exit: same path as SIGTERM, so final state is saved and the
        # stats line printed — the rolling-upgrade primitive
        lease.on_drain = stop.set
    # fleet observability plane, member side (ISSUE 19, rtap_tpu/fleet/,
    # docs/FLEET.md): started BEFORE the standby block so the aggregator
    # watches the whole standby phase — the follow loop, the promotion
    # role change, and the served remainder are one member timeline
    fleet_pub = None
    if args.fleet_join:
        from rtap_tpu.fleet import FleetPublisher

        fhost, _fsep, fport_s = args.fleet_join.rpartition(":")
        frole = "standby" if args.standby else "leader"
        fleet_pub = FleetPublisher(
            (fhost or "127.0.0.1", int(fport_s)),
            f"{frole}-{os.getpid()}", role=frole,
            lease_epoch=lease.epoch if lease is not None else 0,
            push_interval_s=args.fleet_push_interval
            if args.fleet_push_interval is not None else 1.0).start()
        print(f"serve: fleet member {fleet_pub.member!r} pushing to "
              f"{args.fleet_join} every {fleet_pub.push_interval_s}s",
              file=sys.stderr)
    resume_sup = None
    follower = None
    if args.standby:
        # hot standby (ISSUE 8): mirror the leader's journal stream,
        # keep model state warm at the live edge, promote on lease
        # loss — then fall through into normal (leader) serving below
        from rtap_tpu.resilience.replicate import StandbyFollower

        follower = StandbyFollower(
            grp, journal, lease=lease, port=args.replicate_listen,
            alert_path=args.alerts, checkpoint_dir=args.checkpoint_dir,
            learn=not args.freeze, cadence_s=args.cadence,
            stop_event=stop)
        print(f"serve: standby following on port "
              f"{args.replicate_listen} (lease "
              f"{args.lease_file or f'control:{args.control_join}'}, "
              f"timeout {args.lease_timeout}s)", file=sys.stderr)
        outcome = follower.run()
        if outcome == "stopped":
            for sig, handler in prev.items():
                signal.signal(sig, handler)
            journal.close()
            if fleet_pub is not None:
                fleet_pub.close()  # orderly BYE: the fleet sees "left"
            print(json.dumps({"standby": follower.stats(),
                              "stopped": True}))
            return 0
        # promoted: the follower checkpointed the warm fleet and
        # spliced the alert stream; serve the REMAINING budget as the
        # leader (the resume machinery below picks it all up)
        base = max(journal.next_tick, 0)
        if args.checkpoint_dir:
            from rtap_tpu.service.checkpoint import peek_resume_ticks

            base = max(base, peek_resume_ticks(args.checkpoint_dir))
        n_ticks_eff = max(0, args.ticks - base)
        resume_sup = follower.resume_suppression
        lease.start_heartbeat()
        if fleet_pub is not None:
            # the promotion IS a fleet event: same member, new role, the
            # successor lease epoch — failover_soak asserts this exact
            # role_changed sequence against the lease/journal truth
            fleet_pub.set_role("leader", lease_epoch=lease.epoch)
        print(f"serve: standby PROMOTED to leader at tick {base} "
              f"(lease epoch {lease.epoch}, detected in "
              f"{follower.promote_detect_s:.3f}s; {n_ticks_eff} ticks "
              "remain)", file=sys.stderr)
    sender = None
    if args.replicate_to:
        from rtap_tpu.resilience.replicate import ReplicationSender

        host, _sep, port_s = args.replicate_to.rpartition(":")
        sender = ReplicationSender(
            (host or "127.0.0.1", int(port_s)), journal,
            checkpoint_dir=args.checkpoint_dir, chaos=chaos).start()
        journal.tee = sender.tee
        journal.compact_floor = sender.compact_floor
        print(f"serve: replicating journal appends to "
              f"{args.replicate_to} (bounded buffer, drop-oldest)",
              file=sys.stderr)
    # on the device path the native ingest parsers are REQUIRED: a missing
    # compiler must be a loud start-up error, not a silent drop to the
    # 10-50x slower pure-Python parser (reports/ingest_r07.json). The cpu
    # oracle backend keeps auto-detection (hosts without a toolchain).
    native = True if args.backend == "tpu" else None
    if args.http:
        source = HttpPollSource(args.http, ids,
                                track_unknown=args.auto_register)
        close = lambda: None  # noqa: E731
    elif args.ingest_port is not None or args.ingest_shm:
        # wire-speed binary ingest (ISSUE 7, docs/INGEST.md): the RB1
        # batch protocol over persistent sockets and/or a shared-memory
        # ring, addressed by the registry's (shard, group, slot) slot
        # map. Quotas/backfill are admission-control knobs of this path.
        from rtap_tpu.ingest import BinaryBatchSource

        bsrc = BinaryBatchSource(
            grp.slot_map(),
            port=args.ingest_port,
            shm=args.ingest_shm or None,
            quota_rows=args.ingest_quota,
            backfill_horizon=args.ingest_backfill_horizon,
            track_unknown=args.auto_register, native=native).start()
        if bsrc.address is not None:
            bhost, bport = bsrc.address
            print(f"serve: listening for binary batch frames on "
                  f"{bhost}:{bport}", file=sys.stderr)
        if bsrc.ring_name is not None:
            print(f"serve: binary ingest shm ring {bsrc.ring_name!r} "
                  "created (co-located exporters attach by name)",
                  file=sys.stderr)
        source, close = bsrc, bsrc.close
    else:
        # the record's width is the model's, never the first record's: a
        # multi-field model (node, composite) takes {"id", "values", "ts"}
        tcp = TcpJsonlSource(ids, port=args.port,
                             track_unknown=args.auto_register,
                             native=native, n_fields=cfg.n_fields).start()
        host, port = tcp.address
        if cfg.n_fields > 1:
            print(f"serve: a JSONL record carries \"values\": a list of "
                  f"{cfg.n_fields} (null = a missing metric)",
                  file=sys.stderr)
        print(f"serve: listening for JSONL records on {host}:{port}", file=sys.stderr)
        source, close = tcp, tcp.close
    # telemetry exposition (rtap_tpu.obs): a localhost /metrics endpoint for
    # scrapers, and/or a JSONL snapshot file for the no-network hw sessions
    # (--obs-snapshot; $RTAP_OBS_SNAPSHOT is the session runner's default)
    from rtap_tpu.obs import ExpositionServer, default_snapshot_path, write_snapshot

    # per-tick tracing + black-box flight recorder (obs/trace.py,
    # obs/flight.py, docs/POSTMORTEM.md). The span ring also backs the
    # obs server's /trace route, so --obs-port alone enables it.
    trace = None
    flight = None
    if args.trace_out or args.postmortem_dir or args.obs_port is not None:
        from rtap_tpu.obs import TraceRecorder

        # real process identity on the timeline: fleet_trace.py stitches
        # multi-process traces by pid and labels tracks by this name
        trace = TraceRecorder(
            capacity=args.trace_ring,
            process_name=fleet_pub.member if fleet_pub is not None
            else f"rtap-serve-{os.getpid()}")
    if args.postmortem_dir:
        from rtap_tpu.obs import FlightRecorder

        os.makedirs(args.postmortem_dir, exist_ok=True)
        flight = FlightRecorder(
            trace=trace, n_ticks=args.flight_ticks,
            out_dir=args.postmortem_dir,
            info={"command": "serve", "streams": len(ids),
                  "group_size": gsize, "cadence_s": args.cadence,
                  "ticks": args.ticks, "backend": args.backend,
                  "preset": args.preset, "micro_chunk": args.micro_chunk,
                  "pipeline_depth": args.pipeline_depth,
                  "freeze": bool(args.freeze)})
        print(f"serve: flight recorder armed (last {args.flight_ticks} "
              f"ticks -> {args.postmortem_dir})", file=sys.stderr)
    attributor = None
    if args.alert_attribution:
        from rtap_tpu.service.attribution import AlertAttributor

        attributor = AlertAttributor(cfg)
    # model-health observability (obs/health.py, ISSUE 6): the groups
    # above were built with health=args.health, so every chunk already
    # carries the fused on-device aggregates; the tracker folds them
    # into scorecards (GET /health), detects score drift, and raises
    # health incidents onto the alert stream + flight recorder
    health = None
    if args.health:
        from rtap_tpu.obs import HealthTracker

        try:
            health = HealthTracker(
                cfg,
                occupancy_threshold=args.health_occupancy_threshold,
                sparsity_min_frac=args.health_sparsity_min_frac,
                drift_threshold=args.health_drift_threshold,
                drift_min_ticks=args.health_drift_min_ticks)
        except ValueError as e:
            print(f"serve: bad --health parameters: {e}", file=sys.stderr)
            return 2
        print("serve: model-health reducers armed "
              f"(drift tvd>={args.health_drift_threshold} after "
              f"{args.health_drift_min_ticks} ticks, pool occupancy>="
              f"{args.health_occupancy_threshold})", file=sys.stderr)
    # predictive horizon (rtap_tpu/predict/, ISSUE 16): the groups above
    # were built with predict=k, so every chunk already carries the fused
    # predictive-divergence leaf; the tracker folds it into precursor
    # events with a predicted lead time, and with --topology the fuser
    # collapses precursors into one predicted_incident with a predicted
    # blast radius (the correlator's TopologyMap is reused — one parse,
    # one owner)
    predictor = None
    if args.predict:
        from rtap_tpu.predict import BlastFuser, PredictTracker

        try:
            predictor = PredictTracker(
                horizon=predict_k,
                threshold=args.predict_threshold
                if args.predict_threshold is not None else 0.35,
                min_ticks=args.predict_min_ticks
                if args.predict_min_ticks is not None else 12,
                blast=BlastFuser(correlator.topology, seed_streams=ids)
                if correlator is not None else None)
        except ValueError as e:
            print(f"serve: bad --predict parameters: {e}", file=sys.stderr)
            return 2
        print("serve: predictive horizon armed "
              f"(k={predict_k} ticks, miss ewma>={predictor.threshold} "
              f"for {predictor.min_ticks} ticks"
              + (", blast fusion on" if predictor.blast is not None
                 else "") + ")", file=sys.stderr)
    # restart continuity (ISSUE 6 satellite): the run epoch persists
    # beside the incident stream and the gauge survives into every
    # snapshot, so a supervised child's counter resets are attributable
    from rtap_tpu.obs import bump_run_epoch, set_build_info

    run_epoch = bump_run_epoch(args.alerts)
    # always-on identity gauge (ISSUE 19 satellite): every snapshot,
    # scrape, and fleet push says who this process is — a serve reaching
    # this point serves as the leader (a standby already promoted above)
    set_build_info(role="leader", shard=serve_shard, run_epoch=run_epoch,
                   config=cfg)
    if fleet_pub is not None:
        fleet_pub.set_role("leader", run_epoch=run_epoch)
        fleet_pub.attach(health=health, latency=latency, slo=slo_tracker,
                         correlator=correlator, trace=trace)
    if latency is not None:
        # first-class lag gauges (ISSUE 11): polled once per tick into
        # rtap_obs_latency_lag{lag=...} — replication-ack lag while a
        # standby is attached, incident-close lag while correlating
        if sender is not None:
            latency.lag_providers["repl_ack_ticks"] = \
                lambda _t, _ts: sender.ack_lag_ticks()
        if correlator is not None:
            latency.lag_providers["incident_close_s"] = \
                lambda _t, ts: correlator.oldest_open_age_s(ts)
    # fleet observability plane, aggregator side (ISSUE 19): the merged
    # one-pane-of-glass views ride the obs HTTP server (/fleet/*), so
    # --fleet-listen requires --obs-port (enforced in main())
    fleet_agg = None
    if args.fleet_listen is not None:
        from rtap_tpu.fleet import FleetAggregator

        fleet_agg = FleetAggregator(port=args.fleet_listen).start()
        print(f"serve: fleet aggregator on "
              f"{fleet_agg.host}:{fleet_agg.port} (merged views at the "
              "obs server's GET /fleet/* routes)", file=sys.stderr)
    obs_server = None
    if args.obs_port is not None:
        obs_server = ExpositionServer(
            port=args.obs_port, trace=trace,
            flight=flight, health=health,
            correlator=correlator, latency=latency, slo=slo_tracker,
            predict=predictor, fleet=fleet_agg,
            healthz_stale_after_s=max(30.0, 10 * args.cadence)).start()
        ohost, oport = obs_server.address
        print(f"serve: obs telemetry on http://{ohost}:{oport}/metrics",
              file=sys.stderr)
    obs_snapshot = args.obs_snapshot or default_snapshot_path()
    if lease is not None and hasattr(source, "announce_leader") \
            and getattr(source, "address", None) is not None:
        # the lease advertises this leader's RB1 ingest address so a
        # fenced predecessor can re-point its producers (the MAP
        # __leader__ push — docs/INGEST.md)
        lhost, lport = source.address
        lease.set_meta(ingest=f"{lhost}:{lport}")
    jax_tracing = False
    if args.jax_trace:
        # device-side XLA trace paired with the host span timeline: both
        # load into Perfetto
        import jax

        jax.profiler.start_trace(args.jax_trace)
        jax_tracing = True
        if trace is not None:
            # the one point the two clocks share: an `rtap.sync` annotation
            # in the device trace and a `profiler_sync` instant in the host
            # trace, at the same perf_counter reading (obs/trace.py)
            t_sync = time.perf_counter()
            with jax.profiler.TraceAnnotation("rtap.sync"):
                trace.profiler_sync(t_sync)
        print(f"serve: jax profiler tracing to {args.jax_trace}",
              file=sys.stderr)
    try:
        try:
            stats = live_loop(source, grp, n_ticks=n_ticks_eff, cadence_s=args.cadence,
                              alert_path=args.alerts,
                              checkpoint_dir=args.checkpoint_dir,
                              checkpoint_every=args.checkpoint_every,
                              stop_event=stop,
                              pipeline_depth=args.pipeline_depth,
                              dispatch_threads=args.dispatch_threads,
                              learn=not args.freeze,
                              auto_register=args.auto_register,
                              auto_release_after=args.auto_release_after,
                              micro_chunk=args.micro_chunk,
                              chunk_stagger=args.chunk_stagger,
                              chaos=chaos,
                              degradation=degradation,
                              quarantine_restore_after=args.quarantine_restore_after,
                              alert_flush_every=args.alert_flush_every,
                              aot_warmup=args.aot_warmup,
                              trace=trace, flight=flight,
                              attributor=attributor,
                              journal=journal,
                              health=health,
                              lease=lease,
                              resume_suppression=resume_sup,
                              correlator=correlator,
                              latency=latency,
                              slo=slo_tracker,
                              predictor=predictor,
                              fleet=fleet_pub)
        except BaseException as e:  # noqa: BLE001 — dump, then re-raise
            # crash black-box: an exception escaping serve dumps a
            # postmortem bundle BEFORE the traceback, so a dead soak
            # leaves its last N ticks of evidence behind. (Worker-thread
            # faults already surface here: the loop joins its pool and
            # re-raises captured exceptions in the loop thread.)
            if flight is not None:
                flight.record_event({
                    "event": "unhandled_exception",
                    "error": f"{type(e).__name__}: {e}"})
                flight.dump("unhandled_exception")
            raise
        if stats.get("fenced") and lease is not None:
            # fenced out by a promoted standby: re-point any connected
            # RB1 producers at the new leader BEFORE the source closes
            hint = lease.holder_meta().get("ingest")
            if hint and hasattr(source, "announce_leader"):
                source.announce_leader(hint)
                print(f"serve: pushed MAP re-point to new leader {hint}",
                      file=sys.stderr)
        if lease is not None and hasattr(lease, "degraded"):
            stats["control_lease"] = lease.stats()
        if lease is not None and getattr(lease, "draining", False):
            # drained by the control plane: an ORDERLY handoff — release
            # the lease (epoch floor retained server-side) so the standby
            # promotes immediately instead of waiting out staleness
            lease.stop_heartbeat()
            rel = getattr(lease, "release", None)
            if rel is not None:
                rel()
            stats["drained"] = True
            print(f"serve: shard {serve_shard} drained — lease released, "
                  "the standby takes over", file=sys.stderr)
    finally:
        if jax_tracing:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — diagnostics only
                print(f"serve: jax profiler stop failed: {e}",
                      file=sys.stderr)
        for sig, handler in prev.items():
            signal.signal(sig, handler)
        close()
        if sender is not None:
            sender.close()
        if lease is not None:
            lease.stop_heartbeat()
        if journal is not None:
            journal.tee = None
            journal.close()
        if fleet_pub is not None:
            # joined push-thread exit with a best-effort BYE; an abrupt
            # death instead goes stale and the aggregator marks it DOWN.
            # A drain exit says so in the BYE — fleet_report must not
            # read a rolling upgrade as an outage.
            fleet_pub.close(
                reason="drain" if (lease is not None
                                   and getattr(lease, "draining", False))
                else None)
        if obs_server is not None:
            obs_server.close()
        if fleet_agg is not None:
            # after the obs server: no /fleet/* route may race a closed
            # aggregator
            fleet_agg.close()
        if control_plane is not None:
            control_plane.close()
        if args.trace_out and trace is not None:
            # Perfetto-loadable Chrome trace JSON, atomically (tmp +
            # replace): written even on an error path — the timeline of
            # a dying serve is exactly what the postmortem needs. Best
            # effort: must not mask the loop's own exception.
            try:
                tmp = args.trace_out + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(trace.chrome_trace(), f)
                os.replace(tmp, args.trace_out)
                print(f"serve: host trace written to {args.trace_out} "
                      f"({trace.total} records, {trace.dropped} dropped)",
                      file=sys.stderr)
            except OSError as e:
                print(f"serve: trace write failed: {e}", file=sys.stderr)
        if obs_snapshot:
            # final registry snapshot even on an error path: a soak that
            # died mid-run must still leave its telemetry on disk. Best
            # effort — an unwritable path must not mask the loop's own
            # exception (or fail an otherwise-complete run).
            try:
                write_snapshot(obs_snapshot)
            except OSError as e:
                print(f"serve: obs snapshot write failed: {e}",
                      file=sys.stderr)
    # ingest health belongs in the service artifact: a zero-missed-deadline
    # line is only evidence if data was flowing and parsing cleanly
    for attr in ("records_parsed", "parse_errors", "unknown_ids",
                 "native_active", "poll_failures", "polls_short_circuited",
                 "frames_applied", "garbage_bytes", "rows_quota_dropped",
                 "rows_late_dropped", "rows_backfilled",
                 "rows_backpressure_dropped", "rows_stale_epoch"):
        v = getattr(source, attr, None)
        if v is not None:
            stats[attr] = v
    if sender is not None:
        stats["replication"] = sender.stats()
    if args.standby:
        stats["promoted_from_standby"] = True
        stats["promote_detect_s"] = round(follower.promote_detect_s, 3)
        stats["standby"] = follower.stats()
    if stats.get("fenced"):
        from rtap_tpu.resilience.replicate import FENCED_RC

        print(f"serve: FENCED by {lease.holder()!r} at epoch "
              f"{lease.holder_meta().get('epoch')} — exiting rc "
              f"{FENCED_RC}", file=sys.stderr)
        print(json.dumps(stats))
        return FENCED_RC
    print(json.dumps(stats))
    return 0


def _cmd_replay(args: argparse.Namespace) -> int:
    from rtap_tpu.data.synthetic import SyntheticStreamConfig, generate_cluster
    from rtap_tpu.service.loop import replay_streams

    # the generator needs room for post-probation injections
    # (inject_after_frac * length .. length - 50 must be non-empty)
    min_len = 80
    if args.length < min_len:
        print(f"replay: --length must be >= {min_len} (fault injections land "
              "past the probation region)", file=sys.stderr)
        return 2
    scfg = SyntheticStreamConfig(length=args.length, cadence_s=1.0,
                                 anomaly_magnitude=args.magnitude,
                                 noise_phi=0.97, noise_scale=0.5)
    if args.predict_horizon is not None and not args.predict:
        print("replay: --predict-horizon is a predictive-horizon knob; add "
              "--predict", file=sys.stderr)
        return 2
    if args.predict_horizon is not None and args.predict_horizon < 1:
        print("replay: --predict-horizon must be >= 1 (the reducer scores "
              "each tick's prediction against the input that many ticks "
              "later)", file=sys.stderr)
        return 2
    predict_k = _predict_horizon(args)
    predictor = None
    if args.predict:
        from rtap_tpu.predict import PredictTracker

        predictor = PredictTracker(horizon=predict_k)
    streams = generate_cluster(args.nodes, cfg=scfg, seed=args.seed)
    res = replay_streams(streams, _apply_cadence(_sized_cluster(args), args),
                         backend=args.backend,
                         group_size=args.group_size, chunk_ticks=args.chunk_ticks,
                         threshold=args.threshold, alert_path=args.alerts,
                         checkpoint_dir=args.checkpoint_dir,
                         checkpoint_every=args.checkpoint_every,
                         debounce=args.debounce, learn=not args.freeze,
                         predict=predict_k, predictor=predictor)
    if predictor is not None:
        res.throughput["predict"] = predictor.stats()
    print(json.dumps({"streams": len(res.stream_ids), "ticks": len(res.timestamps),
                      **res.throughput}))
    return 0


def _with_argv(argv: list[str], fn) -> int:
    """Run `fn` under a temporary sys.argv (the wrapped mains parse it);
    always restore — a programmatic main(['eval', ...]) call must not leave
    stale args behind for the caller's own argparse users. Propagates the
    wrapped main's int return code (ADVICE.md r3: a failing eval/report must
    not exit 0)."""
    saved = sys.argv
    sys.argv = [saved[0], *argv]
    try:
        return int(fn() or 0)
    finally:
        sys.argv = saved


def _cmd_eval(args: argparse.Namespace) -> int:
    from rtap_tpu.eval import fault_eval

    argv = ["--streams", str(args.streams), "--length", str(args.length),
            "--magnitude", str(args.magnitude), "--backend", args.backend,
            "--debounce", str(args.debounce), "--likelihood", args.likelihood]
    if args.learning_period is not None:
        argv += ["--learning-period", str(args.learning_period)]
    if args.learn_every != 1:
        argv += ["--learn-every", str(args.learn_every)]
    if getattr(args, "learn_burst", 1) != 1:
        argv += ["--learn-burst", str(args.learn_burst)]
    if args.all_kinds:
        argv.append("--all-kinds")
    if args.out:
        argv += ["--out", args.out]
    return _with_argv(argv, fault_eval.main)


def _cmd_nab(args: argparse.Namespace) -> int:
    """BASELINE configs 1-2 as one mechanical command (SURVEY.md §6): load a
    NAB-layout corpus, run the detector family over every file, sweep the
    threshold exhaustively, report normalized per-profile scores."""
    import json as _json

    from rtap_tpu.data.nab_corpus import NAB_CORPUS_ENV, NabFile, load_corpus
    from rtap_tpu.nab.runner import run_corpus

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = args.corpus or os.environ.get(NAB_CORPUS_ENV) \
        or os.path.join(repo, "data", "nab")
    if not os.path.isfile(os.path.join(root, "labels", "combined_windows.json")):
        print(f"nab: no corpus at {root} (need data/**/*.csv + labels/"
              "combined_windows.json). Pass --corpus, set "
              f"${NAB_CORPUS_ENV}, or regenerate the stand-in: "
              "python -c 'from rtap_tpu.data.nab_corpus import "
              "ensure_standin_corpus; ensure_standin_corpus(\"data/nab\")'",
              file=sys.stderr)
        return 2
    files = load_corpus(root, subset=args.subset)
    if not files:
        print(f"nab: corpus at {root} matched no files "
              f"(subset={args.subset!r})", file=sys.stderr)
        return 2
    if args.rows:
        files = [NabFile(f.name, f.timestamps[: args.rows],
                         f.values[: args.rows], f.windows) for f in files]
    cfg = None
    if args.columns:
        from rtap_tpu.config import scaled_nab_preset

        cfg = scaled_nab_preset(args.columns)
    t0 = time.time()
    res = run_corpus(files, cfg=cfg, backend=args.backend)
    wall = time.time() - t0
    scores = {prof: {"threshold": round(thr, 4), "score": round(score, 2)}
              for prof, (thr, score) in res.scores.items()}
    report = {
        "corpus_root": os.path.abspath(root),
        "backend": args.backend,
        "files": [f.name for f in files],
        "records": int(sum(len(f.values) for f in files)),
        "wall_s": round(wall, 1),
        "scores": scores,
    }
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            _json.dump(report, f, indent=2)
    print(_json.dumps(scores))
    return 0


def _cmd_report(args: argparse.Namespace) -> int:
    import os
    import runpy

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    argv = ["--out-dir", args.out_dir, "--streams", str(args.streams),
            "--length", str(args.length)]
    if args.eval_report:
        argv += ["--eval-report", args.eval_report]
    return _with_argv(
        argv,
        lambda: runpy.run_path(os.path.join(repo, "scripts", "report.py"),
                               run_name="__main__"),
    )


def main(argv: list[str] | None = None) -> int:
    maybe_force_cpu()
    ap = argparse.ArgumentParser(prog="python -m rtap_tpu", description=__doc__)
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("serve", help="live scoring loop fed by TCP push or HTTP poll")
    p.add_argument("--streams", default=None,
                   help="comma-separated stream ids to register, or "
                        "@/path/to/file with one id per line (argv has a "
                        "~128 KB single-argument limit; fleets above a few "
                        "thousand streams need the file form)")
    p.add_argument("--http", default=None,
                   help="poll this metrics endpoint each tick (default: TCP listener)")
    p.add_argument("--port", type=int, default=0, help="TCP listen port (0 = ephemeral)")
    p.add_argument("--ingest-port", type=int, default=None,
                   help="listen for the RB1 binary batch protocol on this "
                        "port (0 = ephemeral) instead of per-record JSONL: "
                        "length-prefixed CRC-framed frames of packed "
                        "(slot, value, ts_delta) rows addressed by the "
                        "registry's (shard, group, slot) slot map, decoded "
                        "with zero per-record Python — the wire-speed "
                        "ingest front end (docs/INGEST.md; "
                        "scripts/ingest_bench.py measures it)")
    p.add_argument("--ingest-shm", default=None,
                   help="also create a shared-memory frame ring under this "
                        "name for co-located exporters (same RB1 frames, "
                        "no socket; combine with --ingest-port or use "
                        "alone). The ring is drained once per tick")
    p.add_argument("--ingest-quota", type=int, default=0,
                   help="admission control: max binary-ingest rows per "
                        "tenant per tick (frames carry a tenant header); "
                        "rows beyond the quota are dropped + counted "
                        "(rtap_obs_ingest_quota_dropped_total). 0 = off")
    p.add_argument("--ingest-backfill-horizon", type=int, default=0,
                   help="binary-ingest timestamp alignment: hold emission "
                        "this many SECONDS (of row timestamp) behind the "
                        "newest row seen, so late rows land in the slot "
                        "their timestamp names instead of overwriting "
                        "the latest value; older-than-horizon rows drop "
                        "(counted). At the standard 1 s cadence a second "
                        "is a tick. 0 = latest-wins (JSONL-equivalent "
                        "semantics, the default)")
    p.add_argument("--ticks", type=int, default=60)
    p.add_argument("--cadence", type=float, default=1.0)
    p.add_argument("--preset", choices=("cluster", "nab", "composite",
                                        "categorical", "node"),
                   default="cluster",
                   help="model family: cluster (scalar RDSE, the "
                        "default), nab (NAB-scale), composite (the "
                        "ISSUE 9 multi-field encoder preset — each wire "
                        "record is [value, delta, event-class] fused "
                        "with the hour-of-day ring into one SDR), "
                        "categorical (single event-class/log-template "
                        "field; docs/WORKLOADS.md encoder family), or "
                        "node (one model a node over --fields metrics — "
                        "cpu, mem, net — fused into one SDR; a stream id "
                        "is a node and a JSONL record carries "
                        "\"values\": [..F..], null for a missing metric)")
    p.add_argument("--fields", type=int, default=None, metavar="F",
                   help="with --preset node: metrics a node's model fuses "
                        "(node_preset(F); default 3). The JSONL listener "
                        "takes exactly F values a record")
    p.add_argument("--backend", default="tpu")
    p.add_argument("--group-size", type=int, default=1024,
                   help="streams per device group; len(streams) above this "
                        "serves as multiple interleaved groups per chip "
                        "(SCALING.md: throughput peaks at small G)")
    p.add_argument("--threshold", type=float, default=0.5)
    p.add_argument("--debounce", type=int, default=2,
                   help="alert only after this many consecutive ticks at/"
                        "above threshold (reports/quality_study.json)")
    p.add_argument("--alerts", default=None, help="JSONL alert sink path")
    p.add_argument("--checkpoint-dir", default=None,
                   help="atomic per-group resume checkpoints; restarting "
                        "serve with the same dir resumes every group from "
                        "its recorded tick (service restart survival)")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="checkpoint cadence in ticks (0 = save only on "
                        "exit/shutdown; with --checkpoint-dir, resume-on-"
                        "start always applies)")
    p.add_argument("--journal-dir", default=None,
                   help="per-tick write-ahead journal: every ingested tick "
                        "row is appended (CRC-framed, segment-rotated) "
                        "before scoring, and a restarted serve replays the "
                        "journaled ticks past its checkpoint through the "
                        "normal scoring path — bit-identical catch-up with "
                        "exactly-once alerts across a crash. With a "
                        "journal, --ticks is the run's TOTAL tick budget "
                        "across restarts (docs/RESILIENCE.md durability)")
    p.add_argument("--journal-fsync", default="os",
                   help="journal durability policy: 'os' (page cache; "
                        "survives kill -9, not power loss — default), "
                        "'every-tick' (fsync per tick), or 'every-N' "
                        "(fsync once per N ticks, e.g. every-64)")
    p.add_argument("--journal-segment-bytes", type=int, default=4 << 20,
                   help="journal segment rotation size (bytes)")
    p.add_argument("--journal-max-segments", type=int, default=256,
                   help="hard bound on journal segments on disk (oldest "
                        "evicted + counted; checkpoint compaction normally "
                        "keeps the journal far below this)")
    p.add_argument("--supervise", action="store_true",
                   help="run serve as a supervised child process: abnormal "
                        "deaths (crash, OOM kill, kill -9) restart it with "
                        "exponential backoff under a restart budget, and "
                        "each death lands on the incident stream (needs "
                        "--checkpoint-dir; pair with --journal-dir for "
                        "tick-exact catch-up — scripts/crash_soak.py is "
                        "the acceptance soak)")
    p.add_argument("--supervise-restarts", type=int, default=10,
                   help="supervisor restart budget: abnormal deaths beyond "
                        "this exit 3 instead of restarting")
    p.add_argument("--supervise-backoff", type=float, default=0.5,
                   help="supervisor restart backoff base seconds (doubles "
                        "per consecutive fast death, capped at 30 s; a "
                        "child that stayed up >= 60 s resets the exponent)")
    p.add_argument("--replicate-to", default=None, metavar="HOST:PORT",
                   help="hot-standby replication (docs/RESILIENCE.md "
                        "failover runbook): tee every journal append — "
                        "the exact CRC-framed record bytes — to a "
                        "standby serve listening there, through a "
                        "bounded drop-oldest buffer (a slow standby "
                        "never stalls the tick; rtap_obs_repl_* sizes "
                        "the lag). Needs --journal-dir; journal "
                        "compaction pauses at the standby's ack while "
                        "one is connected")
    p.add_argument("--standby", action="store_true",
                   help="run as the hot standby: listen for a leader's "
                        "replication stream (--replicate-listen), apply "
                        "every shipped tick through the normal scoring "
                        "path (bit-identical warm state), emit nothing, "
                        "and PROMOTE to leader when the lease goes "
                        "stale — splicing the alert stream exactly-once "
                        "and serving the remaining --ticks budget. "
                        "Needs --replicate-listen, --journal-dir, "
                        "--checkpoint-dir, and --lease-file")
    p.add_argument("--replicate-listen", type=int, default=None,
                   help="standby replication listen port (0 = ephemeral)")
    p.add_argument("--lease-file", default=None,
                   help="leadership lease file (shared storage): the "
                        "leader's heartbeat thread refreshes it at "
                        "timeout/3; a standby "
                        "promotes when it goes stale, bumping the "
                        "monotonic fencing epoch — a paused old leader "
                        "that wakes up is fenced out of the alert sink "
                        "and exits rc 7 (docs/RESILIENCE.md)")
    p.add_argument("--lease-timeout", type=float, default=5.0,
                   help="seconds without a lease refresh before a "
                        "standby declares the leader dead and promotes "
                        "(staleness must persist an extra timeout/2 — "
                        "single starved heartbeat reads never false-"
                        "promote; detection ~= 1.5x timeout, so keep "
                        "the timeout <= ~5 cadences for a 10-tick "
                        "takeover budget)")
    p.add_argument("--shard", type=int, default=0,
                   help="this serve's mesh shard index: scopes the "
                        "journal/checkpoint/lease/alert paths per shard "
                        "(the ISSUE 15 shard-resource gate) and names "
                        "the control-plane lease this process claims "
                        "under --control-join")
    p.add_argument("--control-listen", type=int, default=None,
                   metavar="PORT",
                   help="host the fleet CONTROL PLANE on localhost PORT "
                        "(0 = ephemeral): one fencing lease per shard, "
                        "membership/claims, and the shard map. Needs "
                        "--control-journal — every epoch grant is "
                        "journaled write-ahead (RJ framing, fsync before "
                        "the reply), so a kill-9'd control plane "
                        "restarts with epochs strictly monotonic "
                        "(docs/RESILIENCE.md control plane)")
    p.add_argument("--control-journal", default=None, metavar="DIR",
                   help="the control plane's write-ahead journal dir — "
                        "the epoch-durability root for --control-listen")
    p.add_argument("--control-only", action="store_true",
                   help="run ONLY the control plane (no data plane): "
                        "serve leases/membership until SIGTERM, then "
                        "print a stats line — the process "
                        "scripts/fleet_chaos.py kills and restarts. "
                        "Needs --control-listen")
    p.add_argument("--control-join", default=None, metavar="HOST:PORT",
                   help="hold this shard's lease THROUGH the control "
                        "plane at HOST:PORT instead of a --lease-file: "
                        "acquire/heartbeat/fence over control RPCs. An "
                        "unreachable plane degrades — the loop keeps "
                        "ticking on the cached lease for a bounded, "
                        "counted window (--control-grace), then "
                        "self-fences; a standby never promotes on "
                        "control-plane silence (docs/RESILIENCE.md)")
    p.add_argument("--control-grace", type=float, default=None,
                   metavar="SECONDS",
                   help="the bounded cached-lease window under "
                        "--control-join (default max(10x lease timeout, "
                        "30s)): a control plane unreachable past this "
                        "self-fences the holder — fail-safe, never "
                        "split-brain")
    p.add_argument("--learn-every", type=int, default=1,
                   help="learning cadence: learn every k-th tick once the "
                        "likelihood learning_period has passed (SCALING.md "
                        "operating curve; k=1 = full-rate default)")
    p.add_argument("--learn-burst", type=int, default=1,
                   help="burst shape of the thinned cadence: B consecutive "
                        "learn ticks per k*B cycle (same device cost as "
                        "--learn-every alone; preserves TM sequence "
                        "adjacency — SCALING.md burst study)")
    p.add_argument("--learn-full-until", type=int, default=None,
                   help="ticks of full-rate learning before the cadence "
                        "thins (default: the likelihood learning_period — "
                        "the quality-correct bring-up window). 0 measures "
                        "the mature steady state (profile/bench semantics); "
                        "production fleets onboarding gradually never pay "
                        "the whole window at once")
    p.add_argument("--chunk-stagger", action="store_true",
                   help="with --micro-chunk M: rotate chunk boundaries "
                        "across groups (group i flushes at ticks == i mod "
                        "M) so each tick dispatches ~1/M of the fleet "
                        "instead of spiking the whole fleet's chunk work "
                        "onto every M-th tick. Elastic membership and "
                        "periodic checkpoints force a one-tick boundary "
                        "realignment when they fire")
    p.add_argument("--stagger-learn", action="store_true",
                   help="stagger the learning-cadence phase across groups "
                        "(group i learns on ticks == i mod k): spreads the "
                        "fleet's learning load evenly over ticks instead of "
                        "spiking every k-th tick — the 100k-streams-per-chip "
                        "serving shape (SCALING.md)")
    p.add_argument("--pipeline-depth", type=int, default=1,
                   help="2 = collect tick k after dispatching k+1: hides the "
                        "per-group device round trip (remote-chip dispatch "
                        "latency) behind the cadence sleep; alerts lag one "
                        "cadence (reports/live_soak.json measured the cost "
                        "of depth 1 at 16 groups)")
    p.add_argument("--micro-chunk", type=int, default=1,
                   help="batch M consecutive ticks into one device dispatch "
                        "per group: divides the per-program invocation floor "
                        "(~12 ms when the 100k soak was taken, on a chip "
                        "that was not host-local; not re-measured on a "
                        "local one) by M, at <= (depth*M - 1) ticks of alert "
                        "staleness. The 100k-streams-per-chip cadence lever "
                        "(SCALING.md round 5)")
    p.add_argument("--dispatch-threads", type=int, default=1,
                   help="issue per-group dispatch/collect calls from N "
                        "threads: where each dispatch is itself a blocking "
                        "call (~65 ms/group on the remote-attached chip the "
                        "soaks ran on; a host-local chip enqueues "
                        "asynchronously), "
                        "depth-2 pipelining alone cannot help — the round "
                        "trips must overlap each other "
                        "(reports/live_soak_pipelined.json measured depth 2 "
                        "at 16 groups unchanged, p50 1.07 s); output is "
                        "bit-identical to serial dispatch")
    p.add_argument("--columns", type=int, default=None,
                   help="width-scale the cluster preset's SP to N columns "
                        "(scaled_cluster_preset: ratio-preserving k-winners/"
                        "thresholds). The measured density levers: 32 col = "
                        "best f1 on the node-metric family at 1/8 state and "
                        "2.26x throughput; with --learn-every 2 it is the "
                        "135.8k/chip bench headline (SCALING.md model-width "
                        "study). Default: the conservative 256-col preset")
    p.add_argument("--auto-register", action="store_true",
                   help="lazily create a model for every NEW stream id "
                        "seen on the wire — TCP records with unknown ids, "
                        "or unregistered metric KEYS in the HTTP poll "
                        "payload (the reference's per-metric lazy model "
                        "creation / exporter discovery): each claims a "
                        "free pad slot with a fresh model + its own "
                        "likelihood probation, no recompile. Capacity = "
                        "pad slots (--reserve; default one extra group's "
                        "worth)")
    p.add_argument("--reserve", type=int, default=None,
                   help="extra claimable pad-slot capacity for post-start "
                        "registration (rounded up to whole groups; default "
                        "0, or one group's worth with --auto-register)")
    p.add_argument("--auto-release-after", type=int, default=0,
                   help="release a stream's slot after N consecutive silent "
                        "(no-record) ticks — elastic shrink for churning "
                        "clusters; the slot becomes claimable again and a "
                        "returning stream re-registers as a new model. "
                        "Pick N well above ordinary outages: NaN semantics "
                        "keep scoring through gaps, release discards the "
                        "learned context. 0 = never (default)")
    p.add_argument("--chaos-spec", default=None,
                   help="JSON fault-injection schedule (rtap_tpu.resilience."
                        "chaos: {'seed': S, 'faults': [...]} or {'seed': S, "
                        "'generate': {'n_ticks': T, 'n_groups': G, 'rate': "
                        "R}}): scripted source timeouts, dispatch "
                        "exceptions, alert-sink OSErrors, checkpoint write "
                        "failures etc. injected at exactly the scheduled "
                        "ticks — deterministic per seed (docs/RESILIENCE.md)")
    p.add_argument("--degrade", action="store_true",
                   help="shed load under sustained deadline misses, down "
                        "the declared ladder: learn_thin -> score_only -> "
                        "tick_widen, with hysteresis; emits degraded/"
                        "recovered events and the rtap_obs_degradation_"
                        "level gauge (docs/RESILIENCE.md)")
    p.add_argument("--degrade-after", type=int, default=3,
                   help="misses within the 10-tick window that escalate "
                        "the ladder one level (with --degrade)")
    p.add_argument("--degrade-recover-after", type=int, default=15,
                   help="consecutive clean ticks that de-escalate one "
                        "level (with --degrade)")
    p.add_argument("--quarantine-restore-after", type=int, default=0,
                   help="re-load a quarantined group from its last "
                        "checkpoint after this many ticks of cooldown "
                        "(needs --checkpoint-dir; 0 = quarantine is "
                        "permanent for the run). The group loses the ticks "
                        "since its last save; every other group's cadence "
                        "is untouched either way")
    p.add_argument("--aot-warmup", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="compile every knowable (chunk length, config, "
                        "learn-phase) program before tick 0 (service/aot.py) "
                        "so no XLA compile lands inside a scored tick — the "
                        "1h 100k soak's 9 missed deadlines were all warm-up "
                        "compiles; --no-aot-warmup restores lazy compilation")
    p.add_argument("--alert-flush-every", type=int, default=1,
                   help="flush the alert JSONL sink once per N batches "
                        "instead of per batch (1 = per batch, the crash-"
                        "safe default; higher trades at most N batches of "
                        "alert loss on a crash for less write overhead)")
    p.add_argument("--obs-port", type=int, default=None,
                   help="serve the telemetry registry over localhost HTTP "
                        "(GET /metrics = Prometheus v0 text, GET /snapshot "
                        "= JSON); 0 binds an ephemeral port, default: no "
                        "endpoint")
    p.add_argument("--obs-snapshot", default=None,
                   help="append one JSONL telemetry snapshot line to this "
                        "file on exit (default: $RTAP_OBS_SNAPSHOT if set "
                        "— the no-network hw-session surface)")
    p.add_argument("--fleet-join", default=None, metavar="HOST:PORT",
                   help="join the fleet observability plane: push this "
                        "process's full telemetry (registry snapshot, "
                        "health rollup, latency sketch states, SLO "
                        "windows, open-incident digest) to the fleet "
                        "aggregator at HOST:PORT once per "
                        "--fleet-push-interval, off the tick path "
                        "(docs/FLEET.md)")
    p.add_argument("--fleet-listen", type=int, default=None, metavar="PORT",
                   help="host the fleet aggregator: accept member pushes "
                        "on localhost PORT (0 = ephemeral) and serve the "
                        "merged one-pane-of-glass views on the obs "
                        "server's GET /fleet/* routes (requires "
                        "--obs-port; docs/FLEET.md)")
    p.add_argument("--fleet-push-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="fleet telemetry push cadence (default 1.0; needs "
                        "--fleet-join). The member declares 3 missed "
                        "pushes as its DOWN staleness horizon")
    p.add_argument("--trace-out", default=None,
                   help="write the per-tick host span timeline as Chrome "
                        "trace-event JSON to this file on exit (load it in "
                        "ui.perfetto.dev; docs/POSTMORTEM.md). Tracing is "
                        "a bounded in-memory ring, near-zero overhead — "
                        "also served live at GET /trace?last=N with "
                        "--obs-port. Spans: tick, source, membership, "
                        "dispatch, collect, emit, checkpoint, per-group "
                        "dispatch/collect, aot_warm, gc — the ring names "
                        "of the rtap.* vocabulary (obs/trace.py:SPANS; "
                        "docs/TELEMETRY.md), keyed by tick")
    p.add_argument("--trace-ring", type=int, default=65536,
                   help="span-ring capacity in records PER WRITER THREAD "
                        "(~33 B each); older records are overwritten and "
                        "counted in rtap_obs_trace_dropped")
    p.add_argument("--postmortem-dir", default=None,
                   help="arm the black-box flight recorder: the last "
                        "--flight-ticks ticks of spans/events/metric "
                        "deltas auto-dump here as an atomic postmortem "
                        "bundle on group quarantine, degradation-level "
                        "change, missed-tick burst, or a crash "
                        "(scripts/postmortem.py pretty-prints one; "
                        "docs/POSTMORTEM.md is the runbook)")
    p.add_argument("--flight-ticks", type=int, default=240,
                   help="flight-recorder window: how many recent ticks a "
                        "postmortem bundle covers (bounded ring; memory "
                        "is O(flight_ticks * n_groups))")
    p.add_argument("--health", action="store_true",
                   help="model-health observability (docs/TELEMETRY.md "
                        "health section): fused on-device reducers add "
                        "segment-pool occupancy, permanence sketch, SDR "
                        "sparsity, hit rate and score histograms (~200 B/"
                        "group/tick, pure reads — scores and state are "
                        "bit-identical) to every chunk; a HealthTracker "
                        "folds them into per-group scorecards served at "
                        "GET /health, detects score drift by EWMA, and "
                        "raises pool_saturated / sparsity_collapsed / "
                        "score_drift incidents that auto-dump postmortem "
                        "bundles like a quarantine does")
    p.add_argument("--health-occupancy-threshold", type=float, default=0.9,
                   help="segment-pool mean occupancy fraction at/above "
                        "which a group raises pool_saturated (with "
                        "--health; ROADMAP-3 right-sizing signal)")
    p.add_argument("--health-sparsity-min-frac", type=float, default=0.5,
                   help="fraction of the expected active-column density "
                        "(k/C) below which a live group raises "
                        "sparsity_collapsed (with --health)")
    p.add_argument("--health-drift-threshold", type=float, default=0.25,
                   help="total-variation distance between the fast and "
                        "slow EWMA score distributions at/above which a "
                        "group raises score_drift (with --health)")
    p.add_argument("--health-drift-min-ticks", type=int, default=120,
                   help="scored ticks a group must fold before the drift "
                        "detector may fire (the slow EWMA baseline needs "
                        "weight before a distance to it means anything)")
    p.add_argument("--predict", action="store_true",
                   help="predictive horizon (docs/PREDICT.md): a fused "
                        "on-device reducer scores the TM's own "
                        "predictions against the input that actually "
                        "arrives k ticks later (~13 B/stream/tick, pure "
                        "reads — scores and state are bit-identical) and "
                        "a PredictTracker turns sustained predictive "
                        "divergence into precursor events with a "
                        "predicted lead time, BEFORE the anomaly score "
                        "crosses the alert threshold; with --topology, "
                        "precursors fuse into a single "
                        "predicted_incident with a predicted blast "
                        "radius at the FIRST node (GET /predict with "
                        "--obs-port)")
    p.add_argument("--predict-horizon", type=int, default=None,
                   help="prediction lead k in ticks: each tick's "
                        "predicted-active columns are scored against the "
                        "input k ticks later, so precursors carry a "
                        "~k-tick predicted lead (default 8, with "
                        "--predict)")
    p.add_argument("--predict-threshold", type=float, default=None,
                   help="predictive-miss EWMA level at/above which a "
                        "stream counts as diverging (default 0.35, with "
                        "--predict)")
    p.add_argument("--predict-min-ticks", type=int, default=None,
                   help="consecutive diverging scored ticks before a "
                        "precursor fires — edge-triggered hysteresis, "
                        "one event per excursion (default 12, with "
                        "--predict)")
    p.add_argument("--topology", default=None,
                   help="arm topology-aware incident correlation "
                        "(rtap_tpu/correlate/, docs/WORKLOADS.md): a JSON "
                        "topology spec path ({'services': {...}, 'links': "
                        "[...]}) or the literal 'infer' to derive node/"
                        "service adjacency from stream-name prefixes. "
                        "Per-stream alerts on adjacent nodes fold into "
                        "cluster-level 'incident' events on the alert "
                        "stream (member alert_ids, blast-radius node set, "
                        "onset tick, attributed fields), served live at "
                        "GET /incidents. Needs --alerts (incidents ride "
                        "the alert stream)")
    p.add_argument("--correlate-window", type=int, default=None,
                   help="incident correlation quiescence window in "
                        "SECONDS of source timestamp (== ticks at the "
                        "standard 1 s cadence): a cluster's window closes "
                        "after this long without a new member alert — "
                        "re-bursts inside it extend the same incident "
                        "(hysteresis). Size it above the pipeline's alert "
                        "staleness (pipeline_depth * micro_chunk ticks). "
                        "Default 30; needs --topology")
    p.add_argument("--correlate-min-streams", type=int, default=None,
                   help="distinct alerting streams a closed window needs "
                        "to emit an incident; below it the window expires "
                        "silently (the per-stream alert lines already "
                        "told that story). Default 3; needs --topology")
    p.add_argument("--latency", action="store_true",
                   help="arm detection-latency observability (docs/SLO.md; "
                        "docs/TELEMETRY.md latency section): per-tick "
                        "stage waterfalls (source ts -> ingest arrival/"
                        "backfill release -> dispatch -> collect -> "
                        "alert-sink flush) folded into bounded windowed "
                        "quantile sketches, a per-alert end-to-end detect "
                        "sketch observed at sink-write time, and first-"
                        "class replication-ack / incident-close lag "
                        "gauges. Host wall clocks only — zero extra "
                        "device fetches; alert stream and model state are "
                        "byte/bit-identical with the flag off")
    p.add_argument("--latency-window", type=int, default=None,
                   help="quantile-sketch window in ticks (default 120): "
                        "GET /latency reports p50/p95/p99/p99.9 over the "
                        "last one-to-two windows, next to lifetime "
                        "totals. Needs --latency")
    p.add_argument("--slo", action="append", default=None,
                   metavar="NAME=TARGET@pQ",
                   help="declare a latency SLO (repeatable), e.g. "
                        "detect=2s@p99 ('99%% of alerts within 2s of "
                        "their row's source timestamp') or tick=500ms@p95. "
                        "Stages: detect, tick, ingest, dispatch, collect, "
                        "emit. Evaluated with fast/slow multi-window "
                        "burn rates; edge-triggered slo_burn/"
                        "slo_recovered/slo_budget_exhausted events ride "
                        "the alert stream, a fast burn dumps a postmortem "
                        "bundle, and the run's verdict lands in the stats "
                        "line + GET /slo (docs/SLO.md). Needs --latency")
    p.add_argument("--slo-fast-window", type=int, default=None,
                   help="fast burn-rate window in ticks (default 60; "
                        "1 min at 1 s cadence). Needs --slo")
    p.add_argument("--slo-slow-window", type=int, default=None,
                   help="slow burn-rate window in ticks (default 600; "
                        "10 min at 1 s cadence; must be >= the fast "
                        "window). Needs --slo")
    p.add_argument("--alert-attribution", action="store_true",
                   help="per-alert provenance: alert JSONL lines gain a "
                        "top_fields block naming the encoder fields whose "
                        "representation moved most (SDR bucket-overlap "
                        "decode — service/attribution.py); meaningful for "
                        "multivariate models, cheap either way")
    p.add_argument("--jax-trace", default=None,
                   help="wrap the serve window in jax.profiler.trace "
                        "writing the XLA device trace (.xplane.pb) to this "
                        "directory: device ops named by the step's "
                        "`rtap.*` scopes and, on its host plane, the "
                        "loop's ticks and phases (`rtap.loop.*`), the "
                        "stream groups' chunk phases (`rtap.group.*`), "
                        "the ingest handlers (`rtap.ingest.*`) and "
                        "garbage collections (`rtap.host.gc`), joined "
                        "by their arguments `tick`, `group` (first "
                        "stream id), `seq` (obs/trace.py:SPANS; "
                        "docs/TELEMETRY.md). Pairs "
                        "with --trace-out (the same spans in the loop's "
                        "ring, Chrome JSON on perf_counter): the device "
                        "trace's "
                        "`rtap.sync` annotation starts at the host "
                        "trace's `profiler_sync` instant "
                        "(otherData.profiler_sync_perf), which puts the "
                        "two files on one clock")
    p.add_argument("--freeze", action="store_true",
                   help="inference-only serving (NuPIC disableLearning "
                        "parity): SP/TM/classifier state is bit-frozen, raw "
                        "scores and alerts still flow, and the anomaly "
                        "likelihood keeps adapting (it is the score "
                        "normalizer, not model state). Skips the learning "
                        "pass — ~85%% of the fused step on silicon "
                        "(SCALING.md); pair with --checkpoint-dir to serve "
                        "a trained model frozen (the dir becomes strictly "
                        "read-only: frozen serving resumes from it but "
                        "never writes, so replicas can share it)")
    p.set_defaults(fn=_cmd_serve)

    p = sub.add_parser("replay", help="synthetic cluster replay at full speed")
    p.add_argument("--nodes", type=int, default=32, help="nodes x 3 metrics = streams")
    p.add_argument("--length", type=int, default=1500)
    p.add_argument("--magnitude", type=float, default=6.0)
    p.add_argument("--group-size", type=int, default=None)
    p.add_argument("--chunk-ticks", type=int, default=64)
    p.add_argument("--backend", default="tpu")
    p.add_argument("--threshold", type=float, default=0.5)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--alerts", default=None)
    p.add_argument("--checkpoint-dir", default=None,
                   help="atomic per-group resume checkpoints; a rerun with "
                        "the same dir resumes each group from its last "
                        "checkpointed tick (crash recovery)")
    p.add_argument("--checkpoint-every", type=int, default=4,
                   help="checkpoint cadence in collected chunks (with "
                        "--checkpoint-dir)")
    p.add_argument("--debounce", type=int, default=2,
                   help="alert only after this many consecutive ticks at/"
                        "above threshold")
    p.add_argument("--learn-every", type=int, default=1,
                   help="learning cadence: learn every k-th tick once the "
                        "likelihood learning_period has passed (SCALING.md "
                        "operating curve; k=1 = full-rate default)")
    p.add_argument("--learn-burst", type=int, default=1,
                   help="burst shape of the thinned cadence: B consecutive "
                        "learn ticks per k*B cycle (same device cost as "
                        "--learn-every alone; preserves TM sequence "
                        "adjacency — SCALING.md burst study)")
    p.add_argument("--freeze", action="store_true",
                   help="inference-only replay (NuPIC disableLearning "
                        "parity): no SP/TM/classifier updates; likelihood "
                        "still adapts")
    p.add_argument("--columns", type=int, default=None,
                   help="width-scale the cluster preset (see serve --columns)")
    p.add_argument("--predict", action="store_true",
                   help="warm the fleet with the predictive horizon armed "
                        "(docs/PREDICT.md): the checkpoints carry the "
                        "predictor's ring, so serve --predict "
                        "--checkpoint-dir resumes them; the precursor lines "
                        "the history earns go to --alerts")
    p.add_argument("--predict-horizon", type=int, default=None,
                   help="prediction lead k in ticks (default 8, with "
                        "--predict); serve it with the same horizon")
    p.set_defaults(fn=_cmd_replay)

    p = sub.add_parser("eval", help="fault-injection evaluation -> JSON report")
    p.add_argument("--streams", type=int, default=120)
    p.add_argument("--length", type=int, default=1500)
    p.add_argument("--magnitude", type=float, default=6.0)
    p.add_argument("--all-kinds", action="store_true")
    p.add_argument("--backend", default="tpu")
    p.add_argument("--debounce", type=int, default=2)
    p.add_argument("--likelihood", choices=("window", "streaming"),
                   default="streaming",
                   help="likelihood mode; streaming is the production config "
                        "behind the headline artifact (reports/"
                        "fault_eval.json), window the comparison study")
    p.add_argument("--learning-period", type=int, default=None,
                   help="override the likelihood probation length in ticks")
    p.add_argument("--learn-every", type=int, default=1,
                   help="learning cadence: learn every k-th tick once the "
                        "likelihood learning_period has passed (SCALING.md "
                        "operating curve; k=1 = full-rate default)")
    p.add_argument("--learn-burst", type=int, default=1,
                   help="burst shape of the thinned cadence: B consecutive "
                        "learn ticks per k*B cycle (same device cost as "
                        "--learn-every alone; preserves TM sequence "
                        "adjacency — SCALING.md burst study)")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=_cmd_eval)

    p = sub.add_parser(
        "nab",
        help="NAB corpus run: detect -> threshold sweep -> normalized score")
    p.add_argument("--corpus", default=None,
                   help="NAB-layout corpus root (data/**/*.csv + labels/"
                        "combined_windows.json). Default: $RTAP_NAB_CORPUS, "
                        "else the committed stand-in at <repo>/data/nab. "
                        "Point this at the real NAB checkout the moment one "
                        "is available — the run is mechanical (SURVEY.md §6 "
                        "blocker drill)")
    p.add_argument("--subset", default=None,
                   help="relative-path prefix filter, e.g. realAWSCloudwatch")
    p.add_argument("--backend", default="tpu", choices=("tpu", "cpu"),
                   help="tpu = all files as one vmapped device group; cpu = "
                        "per-file oracle (slow at full width)")
    p.add_argument("--columns", type=int, default=None,
                   help="width-scaled NAB model (scaled_nab_preset) instead "
                        "of the 2048-column preset")
    p.add_argument("--rows", type=int, default=None,
                   help="truncate files to this many rows (cheap drives)")
    p.add_argument("--out", default=None, help="report JSON path (default: "
                                               "print scores only)")
    p.set_defaults(fn=_cmd_nab)

    p = sub.add_parser("report", help="matplotlib overlays (metric/likelihood/alerts)")
    p.add_argument("--out-dir", default="reports")
    p.add_argument("--streams", type=int, default=6)
    p.add_argument("--length", type=int, default=900)
    p.add_argument("--eval-report", default=None)
    p.set_defaults(fn=_cmd_report)

    args = ap.parse_args(argv)
    # cheap flag-consistency checks BEFORE backend init: a usage error must
    # surface instantly, not after the backend (and its chip claim) came up
    if getattr(args, "preset", "cluster") != "cluster" and \
            getattr(args, "columns", None) is not None:
        print("serve: --columns applies to the cluster preset only "
              "(the NAB family scales via scaled_nab_preset; the "
              "composite/categorical presets fix their field geometry)",
              file=sys.stderr)
        return 2
    if getattr(args, "fields", None) is not None and \
            getattr(args, "preset", "cluster") != "node":
        print("serve: --fields sizes the node preset only (add --preset "
              "node; the other presets fix their field count)",
              file=sys.stderr)
        return 2
    if getattr(args, "preset", "cluster") == "node":
        if args.fields is None:
            args.fields = 3
        if not 1 <= args.fields <= 64:
            print("serve: --fields must be 1..64 (the native parser's "
                  "widest record)", file=sys.stderr)
            return 2
    if getattr(args, "preset", "cluster") in ("node", "composite") and (
            getattr(args, "http", None)
            or getattr(args, "ingest_port", None) is not None
            or getattr(args, "ingest_shm", None)):
        print(f"serve: --preset {args.preset} scores a record of several "
              "fields a model, which only the TCP JSONL listener carries "
              "(\"values\": [..]); the HTTP poll and the RB1 binary batch "
              "path hold one scalar an id", file=sys.stderr)
        return 2
    if (getattr(args, "correlate_window", None) is not None
            or getattr(args, "correlate_min_streams", None) is not None) \
            and not getattr(args, "topology", None):
        print("serve: --correlate-window/--correlate-min-streams are "
              "incident-correlation knobs; add --topology (a spec path "
              "or 'infer')", file=sys.stderr)
        return 2
    if getattr(args, "topology", None) and not getattr(args, "alerts", None):
        print("serve: --topology needs --alerts — incidents are emitted "
              "on (and resume-recovered from) the alert stream",
              file=sys.stderr)
        return 2
    if (getattr(args, "correlate_window", None) is not None
            and args.correlate_window < 1):
        print("serve: --correlate-window must be >= 1", file=sys.stderr)
        return 2
    if (getattr(args, "correlate_min_streams", None) is not None
            and args.correlate_min_streams < 2):
        print("serve: --correlate-min-streams must be >= 2 (one stream "
              "is a per-stream alert, not an incident)", file=sys.stderr)
        return 2
    if (getattr(args, "predict_horizon", None) is not None
            or getattr(args, "predict_threshold", None) is not None
            or getattr(args, "predict_min_ticks", None) is not None) \
            and not getattr(args, "predict", False):
        print("serve: --predict-horizon/--predict-threshold/"
              "--predict-min-ticks are predictive-horizon knobs; add "
              "--predict", file=sys.stderr)
        return 2
    if getattr(args, "predict_horizon", None) is not None \
            and args.predict_horizon < 1:
        print("serve: --predict-horizon must be >= 1 (the reducer scores "
              "each tick's prediction against the input that many ticks "
              "later)", file=sys.stderr)
        return 2
    if getattr(args, "predict_min_ticks", None) is not None \
            and args.predict_min_ticks < 1:
        print("serve: --predict-min-ticks must be >= 1", file=sys.stderr)
        return 2
    if getattr(args, "checkpoint_dir", None):
        # a fleet is served with the horizon it was warmed with: said here,
        # before any state is made, in the words the resume itself uses
        from rtap_tpu.service.checkpoint import (
            horizon_mismatch, peek_resume_predict)

        saved_k = peek_resume_predict(args.checkpoint_dir)
        want_k = _predict_horizon(args)
        if saved_k is not None and saved_k != want_k:
            print("serve: " + horizon_mismatch(args.checkpoint_dir, saved_k,
                                               want_k), file=sys.stderr)
            return 2
    if getattr(args, "slo", None) and not getattr(args, "latency", False):
        print("serve: --slo declares an objective over the latency "
              "tracker's measurements; add --latency", file=sys.stderr)
        return 2
    if getattr(args, "latency_window", None) is not None \
            and not getattr(args, "latency", False):
        print("serve: --latency-window sizes the quantile-sketch window; "
              "add --latency", file=sys.stderr)
        return 2
    if (getattr(args, "slo_fast_window", None) is not None
            or getattr(args, "slo_slow_window", None) is not None) \
            and not getattr(args, "slo", None):
        print("serve: --slo-fast-window/--slo-slow-window are burn-rate "
              "knobs; add --slo NAME=TARGET@pQ", file=sys.stderr)
        return 2
    if getattr(args, "latency_window", None) is not None \
            and args.latency_window < 1:
        print("serve: --latency-window must be >= 1", file=sys.stderr)
        return 2
    if getattr(args, "http", None) and (
            getattr(args, "ingest_port", None) is not None
            or getattr(args, "ingest_shm", None)):
        print("serve: --http and --ingest-port/--ingest-shm are exclusive "
              "(one source feeds the loop)", file=sys.stderr)
        return 2
    if getattr(args, "port", 0) and (
            getattr(args, "ingest_port", None) is not None
            or getattr(args, "ingest_shm", None)):
        print("serve: --port (JSONL listener) and --ingest-port/--ingest-shm "
              "are exclusive — the binary source replaces the JSONL one; "
              "a JSONL producer pointed at --port would get connection "
              "refused while serve reports healthy", file=sys.stderr)
        return 2
    if (getattr(args, "ingest_quota", 0)
            or getattr(args, "ingest_backfill_horizon", 0)) \
            and getattr(args, "ingest_port", None) is None \
            and not getattr(args, "ingest_shm", None):
        print("serve: --ingest-quota/--ingest-backfill-horizon are binary-"
              "ingest admission knobs; add --ingest-port or --ingest-shm",
              file=sys.stderr)
        return 2
    if getattr(args, "replicate_to", None) and not getattr(args, "journal_dir", None):
        print("serve: --replicate-to ships the write-ahead journal — add "
              "--journal-dir", file=sys.stderr)
        return 2
    if getattr(args, "replicate_to", None) \
            and not getattr(args, "lease_file", None) \
            and not getattr(args, "control_join", None) \
            and not getattr(args, "standby", False):
        print("serve: --replicate-to needs --lease-file (or "
              "--control-join) — a leader without the lease cannot be "
              "fenced, and its standby (which requires the lease) would "
              "find it absent and promote immediately: two live leaders "
              "on one alert sink", file=sys.stderr)
        return 2
    if getattr(args, "replicate_to", None) \
            and not getattr(args, "checkpoint_dir", None):
        print("serve: --replicate-to needs --checkpoint-dir — the "
              "shared checkpoint dir is the reconnect-after-gap "
              "fallback (a standby whose position was compacted or "
              "evicted out of the journal resyncs from it) and the "
              "promotion target", file=sys.stderr)
        return 2
    if getattr(args, "standby", False):
        missing = [f for f, v in (
            ("--replicate-listen", args.replicate_listen is not None),
            ("--journal-dir", bool(args.journal_dir)),
            ("--checkpoint-dir", bool(args.checkpoint_dir)),
            ("--lease-file or --control-join",
             bool(args.lease_file) or bool(getattr(args, "control_join",
                                                   None))),
        ) if not v]
        if missing:
            print(f"serve: --standby needs {', '.join(missing)} (the "
                  "standby mirrors the journal, promotes from the shared "
                  "checkpoint dir, and watches the lease)", file=sys.stderr)
            return 2
        if args.supervise:
            print("serve: --standby under --supervise is unsupported — "
                  "supervise the PAIR from scripts/failover_soak.py "
                  "instead (roles swap across restarts)", file=sys.stderr)
            return 2
    if (getattr(args, "standby", False)
            or getattr(args, "replicate_to", None)) and (
            getattr(args, "auto_register", False)
            or getattr(args, "auto_release_after", 0)):
        print("serve: replication requires a FIXED fleet — "
              "--auto-register/--auto-release-after change membership "
              "mid-stream and the standby's slot addressing would "
              "diverge (elastic membership under replication is future "
              "work)", file=sys.stderr)
        return 2
    if (getattr(args, "standby", False)
            or getattr(args, "replicate_to", None)) \
            and getattr(args, "topology", None):
        print("serve: --topology under replication is unsupported — the "
              "standby buffers would-be alert lines without correlation "
              "state, so a post-failover incident stream could not stay "
              "identical to the leader's (correlation under replication "
              "is future work)", file=sys.stderr)
        return 2
    if (getattr(args, "standby", False)
            or getattr(args, "replicate_to", None)) \
            and getattr(args, "alert_attribution", False):
        print("serve: --alert-attribution under replication is "
              "unsupported — the standby buffers would-be alert lines "
              "WITHOUT the attributor's routing history, so a "
              "post-failover splice could not stay byte-identical to "
              "the leader's stream (attribution under replication is "
              "future work)", file=sys.stderr)
        return 2
    if (getattr(args, "standby", False)
            or getattr(args, "replicate_to", None)) \
            and getattr(args, "predict", False):
        print("serve: --predict under replication is unsupported — the "
              "standby buffers would-be alert lines WITHOUT the "
              "tracker's hysteresis state, so a post-failover precursor "
              "stream could not stay identical to the leader's "
              "(predictive horizon under replication is future work)",
              file=sys.stderr)
        return 2
    if getattr(args, "replicate_listen", None) is not None \
            and not getattr(args, "standby", False):
        print("serve: --replicate-listen is the standby's listen port — "
              "add --standby (the leader side uses --replicate-to)",
              file=sys.stderr)
        return 2
    if getattr(args, "fleet_join", None):
        fhost, fsep, fport_s = args.fleet_join.rpartition(":")
        try:
            fport = int(fport_s)
        except ValueError:
            fport = -1
        if not fsep or not (0 < fport < 65536):
            print(f"serve: bad --fleet-join {args.fleet_join!r} — expected "
                  "HOST:PORT (the fleet aggregator's listen address; an "
                  "empty HOST means 127.0.0.1)", file=sys.stderr)
            return 2
    if getattr(args, "fleet_listen", None) is not None:
        if not (0 <= args.fleet_listen < 65536):
            print("serve: --fleet-listen must be a TCP port "
                  "(0 = ephemeral)", file=sys.stderr)
            return 2
        if getattr(args, "obs_port", None) is None:
            print("serve: --fleet-listen serves the merged fleet views "
                  "on the obs HTTP server's /fleet/* routes; add "
                  "--obs-port", file=sys.stderr)
            return 2
    if getattr(args, "fleet_push_interval", None) is not None:
        if not getattr(args, "fleet_join", None):
            print("serve: --fleet-push-interval paces the fleet "
                  "telemetry push; add --fleet-join HOST:PORT",
                  file=sys.stderr)
            return 2
        if args.fleet_push_interval <= 0:
            print("serve: --fleet-push-interval must be > 0",
                  file=sys.stderr)
            return 2
    if getattr(args, "control_listen", None) is not None:
        if not (0 <= args.control_listen < 65536):
            print("serve: --control-listen must be a TCP port "
                  "(0 = ephemeral)", file=sys.stderr)
            return 2
        if not getattr(args, "control_journal", None):
            print("serve: --control-listen needs --control-journal — the "
                  "write-ahead epoch journal is what keeps fencing "
                  "monotonic across a control-plane crash",
                  file=sys.stderr)
            return 2
    if getattr(args, "control_journal", None) \
            and getattr(args, "control_listen", None) is None:
        print("serve: --control-journal is the control plane's journal "
              "dir; add --control-listen PORT", file=sys.stderr)
        return 2
    if getattr(args, "control_only", False) \
            and getattr(args, "control_listen", None) is None:
        print("serve: --control-only runs just the control plane; add "
              "--control-listen PORT (and --control-journal)",
              file=sys.stderr)
        return 2
    if args.command == "serve" and args.streams is None \
            and not getattr(args, "control_only", False):
        # --streams is only optional for the pure control-plane process
        # (it scores nothing); every data-plane serve must name its fleet
        print("serve: --streams is required (only --control-only runs "
              "without a stream fleet)", file=sys.stderr)
        return 2
    if getattr(args, "control_join", None):
        if getattr(args, "lease_file", None):
            print("serve: --control-join and --lease-file are exclusive "
                  "— one lease authority per process (under a control "
                  "plane, IT owns the shard lease)", file=sys.stderr)
            return 2
        chost, csep, cport_s = args.control_join.rpartition(":")
        try:
            cport = int(cport_s)
        except ValueError:
            cport = -1
        if not csep or not (0 < cport < 65536):
            print(f"serve: bad --control-join {args.control_join!r} — "
                  "expected HOST:PORT (the control plane's listen "
                  "address; an empty HOST means 127.0.0.1)",
                  file=sys.stderr)
            return 2
    if getattr(args, "control_grace", None) is not None:
        if not getattr(args, "control_join", None):
            print("serve: --control-grace bounds the cached-lease window "
                  "under --control-join; add --control-join HOST:PORT",
                  file=sys.stderr)
            return 2
        if args.control_grace <= 0:
            print("serve: --control-grace must be > 0", file=sys.stderr)
            return 2
    if getattr(args, "shard", 0) < 0:
        print("serve: --shard must be >= 0 (the mesh shard index)",
              file=sys.stderr)
        return 2
    if getattr(args, "freeze", False) and getattr(args, "auto_register", False):
        print("serve: --freeze with --auto-register would claim fresh "
              "models that can never learn — a lazily registered stream "
              "would score garbage forever. Register streams in a "
              "learning serve, then freeze; or serve frozen with a fixed "
              "fleet", file=sys.stderr)
        return 2
    if getattr(args, "supervise", False):
        # supervision wraps the WHOLE child serve (including backend
        # init): handle it before this process touches the backend — the
        # parent must never hold the chip its child needs
        if not args.checkpoint_dir:
            print("serve: --supervise needs --checkpoint-dir (a restarted "
                  "child must resume its fleet, not rescore from scratch); "
                  "add --journal-dir for tick-exact catch-up",
                  file=sys.stderr)
            return 2
        if not args.journal_dir:
            print("serve: --supervise without --journal-dir will lose the "
                  "ticks since the last checkpoint on every restart "
                  "(continuity yes, bit-exact catch-up no)", file=sys.stderr)
        from rtap_tpu.resilience.supervisor import (
            Supervisor,
            strip_supervise_flags,
        )

        raw = list(argv) if argv is not None else sys.argv[1:]
        child_cmd = [sys.executable, "-m", "rtap_tpu",
                     *strip_supervise_flags(raw)]
        sup = Supervisor(
            child_cmd, restart_budget=args.supervise_restarts,
            backoff_base_s=args.supervise_backoff,
            backoff_max_s=max(30.0, args.supervise_backoff),
            event_path=args.alerts, postmortem_dir=args.postmortem_dir,
            log=lambda m: print(m, file=sys.stderr))
        print(f"serve: supervising {' '.join(child_cmd[3:])} "
              f"(restart budget {args.supervise_restarts})", file=sys.stderr)
        sup_pub = None
        if getattr(args, "fleet_join", None):
            # the supervisor is a fleet member too: its restart-budget
            # counters and liveness ride the same plane as its child
            # (which inherits --fleet-join and registers separately)
            from rtap_tpu.fleet import FleetPublisher

            shost, _ssep, sport_s = args.fleet_join.rpartition(":")
            sup_pub = FleetPublisher(
                (shost or "127.0.0.1", int(sport_s)),
                f"supervisor-{os.getpid()}", role="supervisor",
                push_interval_s=args.fleet_push_interval
                if args.fleet_push_interval is not None else 1.0).start()
        try:
            return sup.run()
        finally:
            if sup_pub is not None:
                sup_pub.close()
    if getattr(args, "backend", None) == "tpu" \
            and not getattr(args, "control_only", False):
        # the device path: no TPU and no explicit CPU choice is an error
        # at start, never a silent CPU run; compiled programs are reused
        # across service restarts. (--control-only scores nothing and
        # must not claim the chip its data-plane members need.)
        from rtap_tpu.utils.platform import (
            NoAcceleratorError, enable_compile_cache, require_device,
        )

        try:
            require_device()
        except NoAcceleratorError as e:
            print(f"{args.command}: {e}", file=sys.stderr)
            return 1
        enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
