"""Traffic kind `live_predictive`: a service-structured fleet served from a
warmed checkpoint with the predictive horizon armed, while one service in 64
drifts and then cascades.

The bring-up is kind `live_resumed`'s (docs/DEPLOYMENT.md §3 option 2), every
step a bench span of the same name and every step the program's own code —
with the predictor on from the history's first tick:

    traffic           every node's history and the window's records, from --seed
    warm_replay       `service/loop.py:replay_streams` with predict=k and the
                      warming process's tracker: the precursor and
                      predicted_incident lines the history earns are written
                      to the sink as it is replayed, beside its alert lines
    checkpoint_save   the `rtap.checkpoint.save` spans inside that call; the
                      state trees carry pred_ring / pred_miss_ewma / pred_tick0
                      and the tracker's latches and open windows
    release           every warmed group, its registry and its tracker dropped
    state             the listener, and a fresh registry as `serve --predict
                      --health` builds it
    checkpoint_load   `service/loop.py:resume_registry` (refuses another horizon)

and then kind `live`'s serving, phase lock, drain and accounting as they are
(benchmark/traffic_kinds/live.py:_serve), with kind `live_fields`' listener
and recorder and kind `live_resumed`'s history rule, loop wrapper, sink reader
and alert accounting — all three loaded from the cell's root, unedited — the
generator process benchmark/generator_cascade.py, and the loop handed what
`serve --predict --health --topology spec --alerts a` hands it: the tracker
with the blast fuser over the configuration's topology, the health tracker,
the score-driven correlator over the same map, the alert sink, serve's
threshold and debounce, the latency tracker, the alert ids past the
checkpoints' cursor. The tracker is a FRESH one, as a restarted process
builds it: what it knows of the history is what the loop restores from the
checkpoints. No flight recorder, and no checkpoint directory (as
kind `live_resumed`: the loop would write the fleet back inside the window).

What `correct` compares, besides kind `live_resumed`'s (each sampled node's
whole life against benchmark/reference; the alert lines against the
reference's likelihood): the predict leaves the program served for the
sampled nodes, tick by tick, and their ring and EWMA after the run, against
benchmark/reference/predict.py over the reference model's own state; the
precursor lines against the reference's paging rule over the served leaves;
the predicted_incident lines against the reference's fusion of the sink's
own precursors; each sampled node's segment / synapse / cell counts against
the reference's final state; and the health leaf the program SERVED for one
whole group's last tick (the group of the first cascade) against
benchmark/reference/predict.py:health_means over that group's state, read
back after the window. benchmark/check.py takes four numbers from a
kind and decides `correct` from them, so every such breach is counted into
`rows_misrouted` (limit 0) — the log line and record["predictive"] say which
guarantee it broke — and a breach that is a line (a precursor, an incident, a
doubled id) counts in `failed` too, as kind `live_resumed` counts a wrong
alert line.

The detector's quality is recorded and never judged: which origins paged
before their step (`precursor_lead_ticks.live`) and what share of the healthy
nodes fired a precursor in the window (`false_precursor_share.live`)."""

from __future__ import annotations

import concurrent.futures
import gc
import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from benchmark import program
from benchmark.feed import seed_key
from benchmark.generator_cascade import (
    node_ids, offered_cascade, topology_spec)
from benchmark.reference import predict as ref_predict
from benchmark.registry import Registry

LEAVES = ("overlap", "miss_ewma", "pred_col_frac", "scored")

#: node-ticks of reference above which the predict comparison is spread over
#: worker processes (each follows whole nodes; the reference is one Python
#: loop a node and the machines that run a cell have cores to spare)
POOL_ABOVE = 4000


def sample_nodes(seed: int, n_nodes: int, per_service: int, n_sample: int,
                 drawn: list) -> np.ndarray:
    """The nodes `correct` follows, ascending: every origin (at most an
    eighth of the sample), downstream faulted nodes (a quarter), healthy
    siblings of the cascaded services (an eighth), the rest from the other
    services; the first and the last node always."""
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x5A5)))

    def some(pool, n):
        pool = np.asarray(sorted(pool), np.int64)
        return set(rng.choice(pool, size=min(len(pool), n),
                              replace=False).tolist())

    origins = {c["origin"] for c in drawn}
    down = {node for c in drawn for node, _a, _b in c["faults"][1:]}
    in_cascaded = {c["service"] * per_service + j
                   for c in drawn for j in range(per_service)}
    siblings = in_cascaded - origins - down
    others = set(range(n_nodes)) - in_cascaded
    picks = some(origins, n_sample // 8) | some(down, n_sample // 4) \
        | some(siblings, n_sample // 8)
    picks |= some(others, max(0, n_sample - 2 - len(picks)))
    return np.array(sorted(picks | {0, n_nodes - 1}), np.int64)


#: the health leaf's entries the state alone decides (the others need every
#: node's raw score of the tick, which the kind records for the sample only)
HEALTH_LEAVES = ("occ_hist", "seg_occ_frac", "syn_frac", "perm_hist",
                 "perm_conn_frac", "act_col_frac", "pred_cell_frac")

#: nodes of a group read back and counted at once (25 MB of each pool)
HEALTH_BLOCK = 128


def build_predictor(ctx, ids, spec, picks_by_group):
    """The tracker `serve --predict --topology spec` builds, with its blast
    fuser; it keeps, for the sampled slots, the leaves it is handed
    (`recorded`: group -> [(last tick, {leaf: [T, n]})])."""
    from rtap_tpu.correlate import TopologyMap
    from rtap_tpu.predict import BlastFuser, PredictTracker

    pred = ctx.config["predictive"]

    class Recording(PredictTracker):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.recorded = {g: [] for g in picks_by_group}

        def fold(self, group, leaves, tick=-1, ids=None):
            slots = picks_by_group.get(group)
            if slots is not None:
                self.recorded[group].append((int(tick), {
                    k: np.atleast_2d(np.asarray(leaves[k]))[:, slots].copy()
                    for k in LEAVES}))
            super().fold(group, leaves, tick, ids)

    topo = TopologyMap.from_spec(ctx.hooks.get("topology_spec", spec))
    kw = dict(threshold=pred["threshold"], min_ticks=pred["min_ticks"],
              warmup_ticks=pred["warmup_ticks"], rearm_frac=pred["rearm_frac"])
    kw.update(ctx.hooks.get("tracker_kw", {}))  # tests: a control
    return Recording(
        pred["horizon"], blast=BlastFuser(
            topo, window_ticks=pred["window_ticks"], seed_streams=ids), **kw)


def build_health(ctx, cfg, spec):
    """What `serve --health --topology spec` builds beside its registry ->
    (health tracker, correlator). The tracker keeps the last leaf it is
    handed for each group (`served`: group -> (loop tick, leaf))."""
    from rtap_tpu.correlate import IncidentCorrelator, TopologyMap
    from rtap_tpu.obs.health import HealthTracker

    health = ctx.config["health"]

    class Recording(HealthTracker):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.served = {}

        def fold(self, group, leaves, tick=-1):
            self.served[group] = (int(tick), {
                k: np.asarray(leaves[k])[-1].copy() for k in HEALTH_LEAVES})
            super().fold(group, leaves, tick)

    tracker = Recording(
        cfg, occupancy_threshold=health["occupancy_threshold"],
        sparsity_min_frac=health["sparsity_min_frac"],
        drift_threshold=health["drift_threshold"],
        drift_min_ticks=health["drift_min_ticks"])
    topo = TopologyMap.from_spec(ctx.hooks.get("topology_spec", spec))
    return tracker, IncidentCorrelator(topo)


def warm(ctx, cfg, ids, past, ck_dir, alert_path, trace, predictor):
    """kind `live_resumed`'s `warm` with the predictor on -> the history's
    raw scores [H, S]; bench spans `warm_replay` and `checkpoint_save`."""
    from rtap_tpu.data.synthetic import LabeledStream
    from rtap_tpu.service.loop import replay_streams

    resume, alerting = ctx.config["resume"], ctx.config["alerting"]
    H, chunk = past.shape[0], resume["chunk_ticks"]
    ts = resume["history_ts_base"] + np.arange(H, dtype=np.int64)
    t0 = time.perf_counter()
    result = replay_streams(
        [LabeledStream(sid, ts, past[:, i]) for i, sid in enumerate(ids)],
        cfg, backend="tpu", group_size=ctx.config["layout"]["group_size"],
        chunk_ticks=chunk, threshold=alerting["threshold"],
        alert_path=alert_path, learn=resume["learn"], checkpoint_dir=ck_dir,
        checkpoint_every=H // chunk, debounce=alerting["debounce"],
        seed=ctx.seed, trace=trace,
        predict=ctx.config["predictive"]["horizon"], predictor=predictor)
    wall = time.perf_counter() - t0
    saves = [(trace.epoch_perf + r["t0"], r["dur"]) for r in trace.records()
             if r["kind"] == "span" and r["name"] == "checkpoint_save"]
    if len(saves) != ctx.config["layout"]["groups"]:
        raise RuntimeError(
            f"the warm-up saved {len(saves)} groups under the program's "
            f"`rtap.checkpoint.save` span; the layout has "
            f"{ctx.config['layout']['groups']}")
    saved_s = sum(d for _t, d in saves)
    ctx.add_span("warm_replay", t0, wall - saved_s)
    ctx.add_span("checkpoint_save", saves[0][0], saved_s)
    stats = result.throughput
    ctx.say(f"[live_predictive] warmed {len(ids)} nodes over {H} ticks, "
            f"predictor on, in {wall - saved_s:.2f} s "
            f"({len(ids) * H / (wall - saved_s):.0f} node-rows/s) + "
            f"{saved_s:.2f} s of saves; alert lines the history earned "
            f"{stats['alerts']}; predictive events of the history "
            f"{dict(predictor.events_by_kind)}; tm_overflow "
            f"{stats.get('tm_overflow_total')}")
    if stats.get("tm_overflow_total"):
        raise RuntimeError("a learning burst was truncated while warming: "
                           f"tm_overflow {stats['tm_overflow_total']}")
    return np.asarray(result.raw, np.float32), ts


def build_registry(ctx, cfg, ids):
    """A finalized registry as `serve --predict --health` builds it."""
    from rtap_tpu.service.registry import StreamGroupRegistry

    layout, alerting = ctx.config["layout"], ctx.config["alerting"]
    reg = StreamGroupRegistry(cfg, group_size=layout["group_size"],
                              backend="tpu", seed=ctx.seed,
                              threshold=alerting["threshold"],
                              debounce=alerting["debounce"],
                              health=ctx.config["health"]["on"],
                              predict=ctx.config["predictive"]["horizon"])
    for sid in ids:
        reg.add_stream(sid)
    reg.finalize()
    if len(reg.groups) != layout["groups"]:
        raise RuntimeError(f"registry built {len(reg.groups)} groups, "
                           f"configuration says {layout['groups']}")
    return reg


def event_lines(path: str) -> list[tuple[int, dict]]:
    """(byte offset, line) of every precursor and predicted_incident line
    of the sink, in file order."""
    out, at = [], 0
    with open(path, "rb") as f:
        for raw in f:
            if raw.startswith(b'{"event"'):
                line = json.loads(raw)
                if line["event"] in ("precursor", "predicted_incident"):
                    out.append((at, line))
            at += len(raw)
    return out


def run(ctx) -> dict:
    traffic, config, layout = ctx.traffic, ctx.config, ctx.config["layout"]
    cadence, guard = traffic["cadence_s"], traffic["guard_s"]
    if config.get("live_cadence_s") != cadence:
        raise ValueError(
            f"traffic {traffic['name']!r} runs at {cadence} s; configuration "
            f"{config['name']!r} states live_cadence_s "
            f"{config.get('live_cadence_s')!r}")
    if traffic["phase_spread_s"] + 2 * guard > cadence + 1e-9:
        raise ValueError("phase_spread_s + 2 * guard_s must fit in a cadence")
    NG, G = layout["groups"], layout["group_size"]
    S = NG * G
    topo = config["topology"]
    M = topo["nodes_per_service"]
    if topo["services"] * M != S or G % M:
        raise ValueError(
            f"configuration {config['name']!r}: {topo['services']} services "
            f"x {M} nodes is not its layout's {S} streams in whole services "
            f"a group of {G}")
    N = int(ctx.seconds // cadence)
    if N < 1:
        raise ValueError(f"--seconds {ctx.seconds} holds no {cadence} s slot")
    seed, alerting = ctx.seed, config["alerting"]
    # kind `live`'s serving and accounting, kind `live_fields`' listener and
    # recorder, kind `live_resumed`'s bring-up helpers: the cell's own root's
    reg_files = Registry(ctx.root)
    live = reg_files._module("traffic_kinds", "live")
    fields = reg_files._module("traffic_kinds", "live_fields")
    resumed_kind = reg_files._module("traffic_kinds", "live_resumed")
    H = resumed_kind.history_ticks(config)
    cfg = program.model_config(config, control=ctx.control)
    F = cfg.n_fields
    with ctx.span("traffic"):
        past, sent, phi, _send, drawn = offered_cascade(
            seed, S, N, F, traffic["null_share"], traffic["phase_spread_s"],
            traffic["send_quantum_s"], H, M, traffic["signal"],
            traffic["cascade"])
    ids = node_ids(S, M)
    spec = topology_spec(S, M)
    picks = sample_nodes(seed, S, M, config["correct_sample_streams"], drawn)
    recorders = []  # the run's one recorder: its snapshots say who was live

    class Kept(fields.fields_recorder(live, cadence)):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            recorders.append(self)

    live.SnapshotRecorder = Kept
    live.sample_streams = lambda _seed, _n, _sample: picks
    picks_by_group = {g: picks[picks // G == g] % G for g in range(NG)
                      if (picks // G == g).any()}

    from rtap_tpu.obs import LatencyTracker
    from rtap_tpu.service.alerts import scan_alert_ids
    from rtap_tpu.service.loop import resume_registry

    ck_dir = tempfile.mkdtemp(prefix="rtap-bench-ck-")
    alert_path = os.path.join(ck_dir, "alerts.jsonl")
    setup_trace = program.trace_recorder()
    gen = tcp = None
    try:
        warmer = build_predictor(ctx, ids, spec, picks_by_group)
        past_raw, past_ts = warm(ctx, cfg, ids, past, ck_dir, alert_path,
                                 setup_trace, warmer)
        recorded = warmer.recorded
        with ctx.span("release"):
            # replay_streams has returned: its fleet is garbage, and the
            # warming process's tracker goes with it
            del warmer
            gc.collect()
        t_state = time.perf_counter()
        tcp = fields.fields_source(ids, F, require_native=not ctx.allow_cpu)
        registry = build_registry(ctx, cfg, ids)
        if registry.dispatch_ids() != tcp.stream_ids:
            tcp.set_ids(registry.dispatch_ids())
        ctx.add_span("state", t_state, time.perf_counter() - t_state)
        with ctx.span("checkpoint_load"):
            resumed = resume_registry(registry, ck_dir, trace=setup_trace)
        if sorted(resumed.from_ticks.values()) != [H] * NG:
            raise RuntimeError(f"resumed {resumed.from_ticks}; {NG} groups "
                               f"at tick {H} were saved")
        cursor = resumed.alerts_offset
        if cursor is None:
            raise RuntimeError("the checkpoints carry no alert cursor")
        restart_at = os.path.getsize(alert_path)
        with ctx.span("generator_start"):
            env = dict(os.environ, PYTHONPATH=os.pathsep.join(
                [ctx.root, os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
            gen = subprocess.Popen(
                [sys.executable, "-m", "benchmark.generator_cascade",
                 "--fields", str(F), "--null-share", str(traffic["null_share"]),
                 "--history", str(H), "--nodes-per-service", str(M),
                 "--signal", json.dumps(traffic["signal"]),
                 "--cascade", json.dumps(traffic["cascade"]),
                 "--port", str(tcp.address[1]), "--seed", str(seed),
                 "--streams", str(S), "--slots", str(N),
                 "--cadence", str(cadence),
                 "--spread", str(traffic["phase_spread_s"]),
                 "--quantum", str(traffic["send_quantum_s"]),
                 "--ts-base", str(traffic["row_ts_base"]),
                 "--hold", str(int(traffic["hold_until_snapshot"]))],
                cwd=ctx.root, env=env, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True)
            if gen.stdout.readline().strip() != "READY":
                raise RuntimeError("the generator process did not come up")
        latency = LatencyTracker(cadence_s=cadence) if alerting["latency"] \
            else None
        # a restarted serve's own trackers: the loop hands this predictor
        # the latches and open windows the checkpoints carry
        predictor = build_predictor(ctx, ids, spec, picks_by_group)
        health, correlator = build_health(ctx, cfg, spec)
        live.program = resumed_kind.ResumedProgram(
            alert_path=alert_path,
            alert_flush_every=alerting["alert_flush_every"], latency=latency,
            resume_suppression=scan_alert_ids(alert_path, cursor),
            predictor=predictor, health=health, correlator=correlator)
        record = live._serve(ctx, registry, tcp, gen, sent, phi, N)
        record["checkpoint_dir"] = ck_dir
        if "alert_sink" in ctx.hooks:
            ctx.hooks["alert_sink"](alert_path, restart_at)  # tests
        fleet = SimpleNamespace(
            past=past, past_raw=past_raw, past_ts=past_ts,
            offered=np.ones((N, S), bool), phi=phi, ids=ids,
            drawn=[(node, "cascade", a, b)
                   for c in drawn for node, a, b in c["faults"]],
            alert_path=alert_path, cursor=cursor, restart_at=restart_at)
        resumed_kind._account(ctx, record, setup_trace, latency, fleet)
        for g, folds in predictor.recorded.items():
            recorded[g] = recorded[g] + folds
        _account(ctx, record, registry, recorded, fleet, drawn, spec, H)
        _account_health(ctx, record, registry, health, recorders[0],
                        drawn[0]["origin"] // G)
        return record
    finally:
        if gen is not None:
            try:
                gen.stdin.write("STOP\n")
                gen.stdin.flush()
            except (BrokenPipeError, ValueError, OSError):
                pass
            try:
                gen.wait(timeout=10)
            except subprocess.TimeoutExpired:
                gen.kill()
                gen.wait()
        if tcp is not None:
            tcp.close()
        shutil.rmtree(ck_dir, ignore_errors=True)


def served_leaves(recorded: list, n_ticks: int, n_slots: int) -> dict | None:
    """One group's recorded folds -> {leaf: [n_ticks, n_slots]}, row t the
    group tick t; None where the folds do not cover ticks 0..n_ticks-1 once
    each."""
    out = {k: np.full((n_ticks, n_slots), np.nan, np.float32) for k in LEAVES}
    out["scored"] = np.zeros((n_ticks, n_slots), bool)
    seen = np.zeros(n_ticks, int)
    for last, leaves in recorded:
        T = leaves["scored"].shape[0]
        t0 = last - (T - 1)
        if t0 < 0 or last >= n_ticks:
            return None
        seen[t0:last + 1] += 1
        for k in LEAVES:
            out[k][t0:last + 1] = leaves[k]
    return out if (seen == 1).all() else None


def follow_all(model: dict, sample: list, horizon: int) -> list[dict]:
    """benchmark/reference/predict.py:follow of every sampled node, on
    worker processes where that is worth their start."""
    jobs = [(model, s["seed"], s["ts"], s["values"], horizon) for s in sample]
    work = sum(len(s["ts"]) for s in sample)
    workers = min(8, os.cpu_count() or 1, len(jobs))
    if work <= POOL_ABOVE or workers < 2:
        return [ref_predict.follow(*job) for job in jobs]
    # spawned, never forked: this process holds the device and its threads
    with concurrent.futures.ProcessPoolExecutor(
            max_workers=workers,
            mp_context=multiprocessing.get_context("spawn")) as pool:
        return list(pool.map(ref_predict.follow, *zip(*jobs)))


def _gap(a, b) -> float:
    """Largest |a - b|, NaN equal to NaN and to nothing else."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    both = np.isnan(a) & np.isnan(b)
    gap = np.where(both, 0.0, np.abs(a - b))
    return float(np.where(np.isnan(gap), np.inf, gap).max(initial=0.0))


def _account(ctx, record, registry, recorded, fleet, drawn, spec, H) -> None:
    """The predictive guarantees, after kind `live_resumed`'s accounting
    (each sampled node's `ts` / `values` / `raw` already hold its whole
    life): the served leaves, the final ring and EWMA and the health counts
    against the reference; the precursor lines against the rule; the
    incident lines against the fusion; then what the detector did."""
    t0 = time.perf_counter()
    config, pred = ctx.config, ctx.config["predictive"]
    tol = config["precision"]["predict_tolerance"]
    ids, groups = fleet.ids, registry.groups
    G = groups[0].G
    node_of = {sid: i for i, sid in enumerate(ids)}
    sample = record["sample"]
    n_ticks = len(sample[0]["ts"]) if sample else 0

    # ---- predict: served leaves, final ring and EWMA, health counts ----
    refs = follow_all(config["model"], sample, pred["horizon"])
    by_group = {g: served_leaves(rec, n_ticks, len(rec[0][1]["scored"][0])
                                 if rec else 0)
                for g, rec in recorded.items()}
    slot_at: dict = {}  # node -> (group, column among the group's picks)
    for g in recorded:
        cols = [s["stream"] for s in sample if s["stream"] // G == g]
        for j, node in enumerate(sorted(cols)):
            slot_at[node] = (g, j)
    connected = ref_predict.connected_quanta(config["model"])
    gap = 0.0
    bits_wrong = lost = health_wrong = 0
    served_by_node = {}
    for s, ref in zip(sample, refs):
        node = s["stream"]
        g, j = slot_at[node]
        if by_group[g] is None:
            lost += 1  # the folds of this group do not cover its ticks
            continue
        served = {k: by_group[g][k][:, j] for k in LEAVES}
        served_by_node[node] = served
        bits_wrong += int((served["scored"] != ref["scored"]).sum())
        for k in ("overlap", "miss_ewma", "pred_col_frac"):
            gap = max(gap, _gap(served[k], ref[k]))
        rows = program.state_rows(
            groups[g], node % G, ("pred_ring", "pred_miss_ewma", "seg_last",
                                  "presyn", "syn_perm", "prev_active",
                                  "active_seg"))
        bits_wrong += int((np.asarray(rows["pred_ring"], bool)
                           != ref["pred_ring"]).sum())
        gap = max(gap, _gap(rows["pred_miss_ewma"], ref["pred_miss_ewma"]))
        counts = ref_predict.stream_health(rows, connected)
        health_wrong += sum(counts[k] != ref["health"][k] for k in counts)
    leaf_breaches = bits_wrong + lost + health_wrong + int(gap > tol)

    # ---- precursors: the rule over the served leaves; ids once ----
    events = event_lines(fleet.alert_path)
    all_ids = [line["alert_id"] for _at, line in events]
    doubled = len(all_ids) - len(set(all_ids))
    covered = sum(1 for at, line in events
                  if at >= fleet.restart_at and line["tick"] < H)
    fired = {}  # node -> ticks of its precursor lines
    for _at, line in events:
        if line["event"] == "precursor":
            fired.setdefault(node_of[line["stream"]], set()).add(line["tick"])
    precursors_wrong = due_n = 0
    for node, served in served_by_node.items():
        due = set(ref_predict.precursor_ticks(
            served["scored"], served["miss_ewma"], pred["threshold"],
            pred["min_ticks"], pred["warmup_ticks"], pred["rearm_frac"]))
        due_n += len(due)
        precursors_wrong += len(due ^ fired.get(node, set()))

    # ---- incidents: the fusion of the sink's own precursors ----
    cluster_of = {node: svc for svc, nodes in spec["services"].items()
                  for node in nodes}
    expected = ref_predict.fuse(
        [(line["stream"], line["tick"]) for _at, line in events
         if line["event"] == "precursor"],
        cluster_of.__getitem__, spec["services"], pred["window_ticks"])
    want = {(i["cluster"], i["tick"], i["first_node"], i["blast_radius"])
            for i in expected}
    got = {(line["cluster"], line["tick"], line["first_node"],
            frozenset(line["blast_radius"]))
           for _at, line in events if line["event"] == "predicted_incident"}
    incidents_wrong = len(want ^ got)

    line_breaches = precursors_wrong + incidents_wrong + doubled + covered
    record["failed"] += line_breaches
    record["rows_misrouted"] += leaf_breaches + line_breaches

    # ---- what the detector did with the cascade (recorded, not judged) ----
    after = {(node_of[line["stream"]], line["tick"]) for at, line in events
             if line["event"] == "precursor" and at >= fleet.restart_at}
    # the loop's tick j scored group tick H + j, and slot k was due to be
    # scored by the loop's tick k + 1
    leads = []
    for c in drawn:
        step = H + 1 + c["faults"][0][1]  # group tick of the origin's step
        ticks = [t for node, t in after if node == c["origin"] and t < step]
        if ticks:
            leads.append(step - min(ticks))
    faulted = {node for c in drawn for node, _a, _b in c["faults"]}
    healthy = len(ids) - len(faulted)
    false_nodes = {node for node, _t in after} - faulted
    in_window = [line for at, line in events if at >= fleet.restart_at]
    record["row_latency_ms"].update(
        precursor_lead_ticks=float(np.mean(leads)) if leads else None,
        false_precursor_share=100.0 * len(false_nodes) / healthy
        if after else None)
    record["predictive"] = {
        "predict_max_abs_diff": gap, "predict_bits_wrong": bits_wrong,
        "folds_lost": lost, "health_counts_wrong": health_wrong,
        "sampled_precursors_due": due_n,
        "precursors_wrong": precursors_wrong,
        "incidents_wrong": incidents_wrong, "doubled": doubled,
        "for_covered_ticks": covered, "event_lines": len(events),
        "event_lines_after_restart": len(in_window),
        "paged_clusters": sorted({c for c, _t, _n, _r in got}),
        "origins_paged_early": len(leads), "lead_ticks": leads,
        "false_precursor_nodes": len(false_nodes),
        "account_s": time.perf_counter() - t0,
    }
    kinds = [line["event"] for line in in_window]
    ok = "ok" if gap <= tol else "FAILED"
    ctx.say(
        f"[live_predictive] {len(sample)} sampled nodes x {n_ticks} ticks "
        f"against benchmark/reference/predict.py in "
        f"{time.perf_counter() - t0:.2f}s: predict_max_abs_diff {gap:.6g} "
        f"(limit {tol:g}) {ok}; predict_bits_wrong {bits_wrong} (limit 0); "
        f"groups whose folds lost a tick {lost}; health counts that differ "
        f"{health_wrong}; sampled precursors due {due_n}, decisions that "
        f"differ {precursors_wrong}; predicted incidents {len(got)}, that "
        f"differ from the fusion {incidents_wrong}; doubled ids {doubled}; "
        f"lines for ticks the checkpoint covered {covered}; breaches "
        f"counted into rows_misrouted {leaf_breaches + line_breaches}, into "
        f"failed {line_breaches}")
    ctx.say(
        f"[live_predictive] the detector (recorded, not judged): predictive "
        f"lines in the sink {len(events)} ({len(events) - len(in_window)} "
        f"from the history, {kinds.count('precursor')} precursors and "
        f"{kinds.count('predicted_incident')} predicted incidents after the "
        f"restart); cascades {len(drawn)}, origins that paged before their "
        f"step {len(leads)} (lead ticks {leads}); faulted nodes "
        f"{len(faulted)}, of them with a precursor in the window "
        f"{len({n for n, _t in after} & faulted)}; healthy nodes with a "
        f"precursor in the window {len(false_nodes)} of {healthy}")


def _account_health(ctx, record, registry, health, recorder, g) -> None:
    """The `health` guarantee on the leaf the program served: group `g`'s
    last folded leaf against benchmark/reference/predict.py:health_means
    over the whole group's state, read back now (the loop has ended: the
    state is the post-step state of the group's last tick), with the live
    nodes of that tick from the recorder's snapshot of it. A leaf entry off
    by more than `health_tolerance` (a count: at all) is a breach."""
    t0 = time.perf_counter()
    config = ctx.config
    model, tol = config["model"], config["precision"]["health_tolerance"]
    group = registry.groups[g]
    G = group.G
    if g not in health.served:
        record["rows_misrouted"] += 1
        record["predictive"].update(health_leaf_wrong=1,
                                    health_leaf_max_abs_diff=None)
        ctx.say(f"[live_predictive] group {g} served no health leaf: FAILED")
        return
    tick, leaf = health.served[g]
    live = np.isfinite(recorder.snap_values[tick][g * G:(g + 1) * G]).any(-1)
    connected = ref_predict.connected_quanta(model)
    one = ref_predict.one_quanta(model)
    blocks = []
    for lo in range(0, G, HEALTH_BLOCK):
        rows = program.state_rows(
            group, slice(lo, min(G, lo + HEALTH_BLOCK)),
            ("seg_last", "presyn", "syn_perm", "prev_active", "active_seg"))
        blocks.append(ref_predict.health_counts(rows, connected, one))
    counts = {k: np.concatenate([b[k] for b in blocks]) for k in blocks[0]}
    want = ref_predict.health_means(counts, live, model)
    gaps = {k: _gap(leaf[k], want[k]) for k in HEALTH_LEAVES}
    wrong = sorted(k for k, d in gaps.items()
                   if d > (0 if k == "occ_hist" else tol))
    record["rows_misrouted"] += len(wrong)
    worst = max(d for k, d in gaps.items() if k != "occ_hist")
    record["predictive"].update(
        health_leaf_wrong=len(wrong), health_leaf_max_abs_diff=worst,
        health_leaf_group=g, health_leaf_tick=tick,
        health_leaf_live=int(live.sum()),
        health_leaf={k: np.asarray(leaf[k]).tolist() for k in HEALTH_LEAVES},
        health_account_s=time.perf_counter() - t0)
    ctx.say(
        f"[live_predictive] the health leaf served for group {g} at loop "
        f"tick {tick} ({int(live.sum())} of {G} nodes live) against "
        f"benchmark/reference/predict.py:health_means over the group's "
        f"state, read back in {time.perf_counter() - t0:.2f}s: "
        f"health_leaf_max_abs_diff {worst:.6g} (limit {tol:g}), occ_hist "
        f"bins that differ {int(gaps['occ_hist'])} (limit 0); entries in "
        f"breach {wrong or 0}, counted into rows_misrouted; seg_occ_frac "
        f"{float(leaf['seg_occ_frac']):.6f} syn_frac "
        f"{float(leaf['syn_frac']):.6f} perm_conn_frac "
        f"{float(leaf['perm_conn_frac']):.6f} act_col_frac "
        f"{float(leaf['act_col_frac']):.6f} pred_cell_frac "
        f"{float(leaf['pred_cell_frac']):.6f}")
