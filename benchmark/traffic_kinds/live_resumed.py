"""Traffic kind `live_resumed`: a fleet served from a warmed checkpoint,
alerting while its nodes take faults.

Set-up is the documented bring-up of a fleet (docs/DEPLOYMENT.md §3 option 2),
every step a bench span of its own name and every step the program's own code:

    traffic           every node's history and the window's records, from --seed
    warm_replay       the fleet made from --seed and warmed on each node's
                      history through `service/loop.py:replay_streams`, with
                      a checkpoint directory and the alert sink (the lines
                      the history earns are written as it is replayed)
    checkpoint_save   the `rtap.checkpoint.save` spans inside that call, one
                      a group (their seconds are taken out of `warm_replay`)
    release           every warmed group and its registry dropped: nothing of
                      that process's fleet stays on the device
    state             the listener, and a fresh registry as `serve` builds it
    checkpoint_load   `service/loop.py:resume_registry`: the resume a
                      restarted `serve --checkpoint-dir` runs, one
                      `rtap.checkpoint.load` span a group

and then kind `live`'s serving, phase lock, drain and accounting as they are
(benchmark/traffic_kinds/live.py:_serve, loaded from the cell's root), with
kind `live_fields`' listener and recorder, the generator process
benchmark/generator_faults.py, and the loop handed the alert sink, serve's
threshold and debounce, the latency tracker and the alert ids found past the
checkpoints' cursor. The loop is NOT handed the checkpoint directory: it
would write the fleet back at its exit (serve's save at shutdown), inside the
measured window, and load it a second time.

What `correct` compares is each sampled node's whole life — its history, then
every served tick — so benchmark/check.py's four numbers judge the resume.
The alert lines are held against benchmark/reference/likelihood.py here: a
decision that differs, a missing line, a doubled id or a line for a tick the
checkpoint covered counts in `failed`. A record a killed node never offered
is not attempted."""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from types import SimpleNamespace

import numpy as np

from benchmark import program
from benchmark.feed import seed_key, stream_ids
from benchmark.generator_faults import offered_fleet
from benchmark.reference import likelihood as ref_likelihood
from benchmark.registry import Registry


class ResumedProgram:
    """benchmark/program.py as kind `live` reaches it, its loop called with
    what a restarted, alerting `serve` hands it (`loop_kw`)."""

    def __init__(self, **loop_kw):
        self.loop_kw = loop_kw

    def __getattr__(self, name):
        return getattr(program, name)

    def live_loop(self, source, registry, n_ticks, cadence_s, traffic, trace,
                  stop_event) -> dict:
        from rtap_tpu.service.loop import live_loop

        return live_loop(source, registry, n_ticks=n_ticks,
                         cadence_s=cadence_s,
                         pipeline_depth=traffic["pipeline_depth"],
                         micro_chunk=traffic["micro_chunk"],
                         learn=traffic["learn"], aot_warmup=True, trace=trace,
                         stop_event=stop_event, **self.loop_kw)


def history_ticks(config: dict) -> int:
    """The history a node's model is warmed on: the likelihood's probation
    plus the stated margin, in whole chunks — computed from the model, and
    held against the number the file states."""
    resume = config["resume"]
    chunk = resume["chunk_ticks"]
    need = ref_likelihood.probation(config["model"]["likelihood"]) \
        + resume["history_margin_ticks"]
    ticks = -(-need // chunk) * chunk
    if resume["history_ticks"] != ticks:
        raise ValueError(
            f"configuration {config['name']!r} states history_ticks "
            f"{resume['history_ticks']}; its model's probation and margin "
            f"come to {ticks}")
    return ticks


def sample_nodes(seed: int, n_nodes: int, n_sample: int, faulted) -> np.ndarray:
    """The nodes `correct` and the alert comparison follow, ascending: half
    of the sample from the faulted nodes, the rest from the others, the first
    and the last node always."""
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x5A4)))
    faulted = np.asarray(sorted(faulted), np.int64)
    others = np.setdiff1d(np.arange(n_nodes), faulted)
    n_hit = min(len(faulted), n_sample // 2)
    n_rest = min(len(others), max(0, n_sample - n_hit - 2))
    picks = set(rng.choice(faulted, size=n_hit, replace=False).tolist()) \
        | set(rng.choice(others, size=n_rest, replace=False).tolist())
    return np.array(sorted(picks | {0, n_nodes - 1}), np.int64)


def fleet_recorder(live, fields, cadence_s: float, offered: np.ndarray):
    """kind `live_fields`' recorder for a fleet some of whose nodes offer
    nothing for a while. A slot a node never offered is accounted for as it
    comes up; left to the recorder's own search, a killed node would hold the
    search's lower end at its kill for the ten slots it is gone, every
    snapshot's match would walk those slots for all the nodes, and the tick
    would read ~1.7 ms longer for as long as any node is down (PERF.md §6,
    PR 46)."""
    N, S = offered.shape
    # upcoming[k, i]: node i's first offered slot at or after k (N: none)
    upcoming = np.full((N + 1, S), N, np.int64)
    for k in range(N - 1, -1, -1):
        upcoming[k] = np.where(offered[k], k, upcoming[k + 1])
    nodes = np.arange(S)

    class FleetRecorder(fields.fields_recorder(live, cadence_s)):
        def _match(self, tick: int, values: np.ndarray) -> None:
            self.next_slot = upcoming[self.next_slot, nodes]
            super()._match(tick, values)

    return FleetRecorder


def warm(ctx, cfg, ids, past, ck_dir, alert_path, trace):
    """The offline warm-up -> the history's raw scores [H, S]; the bench
    spans `warm_replay` and `checkpoint_save`."""
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
        seed=ctx.seed, trace=trace)
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
    ctx.say(f"[live_resumed] warmed {len(ids)} nodes over {H} ticks in "
            f"{wall - saved_s:.2f} s ({len(ids) * H / (wall - saved_s):.0f} "
            f"node-rows/s) + {saved_s:.2f} s of saves; alert lines the "
            f"history earned {stats['alerts']}; tm_overflow "
            f"{stats.get('tm_overflow_total')}")
    if stats.get("tm_overflow_total"):
        raise RuntimeError("a learning burst was truncated while warming: "
                           f"tm_overflow {stats['tm_overflow_total']}")
    return np.asarray(result.raw, np.float32), ts


def build_registry(ctx, cfg):
    """A finalized registry as `serve` builds it, with the configuration's
    alerting (benchmark/program.py:build_registry hands over neither the
    threshold nor the debounce)."""
    from rtap_tpu.service.registry import StreamGroupRegistry

    layout, alerting = ctx.config["layout"], ctx.config["alerting"]
    reg = StreamGroupRegistry(cfg, group_size=layout["group_size"],
                              backend="tpu", seed=ctx.seed,
                              threshold=alerting["threshold"],
                              debounce=alerting["debounce"])
    for sid in stream_ids(layout["streams"]):
        reg.add_stream(sid)
    reg.finalize()
    if len(reg.groups) != layout["groups"]:
        raise RuntimeError(f"registry built {len(reg.groups)} groups, "
                           f"configuration says {layout['groups']}")
    return reg


def sink_lines(path: str) -> list[tuple[int, str, int]]:
    """(byte offset, node id, group tick) of every alert line of the sink,
    in file order; the loop's structured event lines (a missed tick, say)
    share the sink and are no alert."""
    out, at = [], 0
    with open(path, "rb") as f:
        for raw in f:
            line = json.loads(raw)
            if "event" not in line:
                _group, node, tick = line["alert_id"].rsplit(":", 2)
                out.append((at, node, int(tick)))
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
    N = int(ctx.seconds // cadence)
    if N < 1:
        raise ValueError(f"--seconds {ctx.seconds} holds no {cadence} s slot")
    seed, alerting = ctx.seed, config["alerting"]
    H = history_ticks(config)
    cfg = program.model_config(config, control=ctx.control)
    F = cfg.n_fields
    with ctx.span("traffic"):
        past, sent, offered, phi, _send, drawn = offered_fleet(
            seed, S, N, F, traffic["null_share"], traffic["phase_spread_s"],
            traffic["send_quantum_s"], H, traffic["faults"])
    ids = stream_ids(S)
    picks = sample_nodes(seed, S, config["correct_sample_streams"],
                         {node for node, _k, _a, _b in drawn})
    # kind `live`'s serving and accounting and kind `live_fields`' listener
    # and recorder, from the cell's own root
    reg_files = Registry(ctx.root)
    live = reg_files._module("traffic_kinds", "live")
    fields = reg_files._module("traffic_kinds", "live_fields")
    live.SnapshotRecorder = fleet_recorder(live, fields, cadence, offered)
    live.sample_streams = lambda _seed, _n, _sample: picks

    from rtap_tpu.obs import LatencyTracker
    from rtap_tpu.service.alerts import scan_alert_ids
    from rtap_tpu.service.loop import resume_registry

    ck_dir = tempfile.mkdtemp(prefix="rtap-bench-ck-")
    alert_path = os.path.join(ck_dir, "alerts.jsonl")
    setup_trace = program.trace_recorder()
    gen = tcp = None
    poll_stop = threading.Event()
    try:
        past_raw, past_ts = warm(ctx, cfg, ids, past, ck_dir, alert_path,
                                 setup_trace)
        with ctx.span("release"):
            gc.collect()  # replay_streams has returned: its fleet is garbage
        t_state = time.perf_counter()
        tcp = fields.fields_source(ids, F, require_native=not ctx.allow_cpu)
        registry = build_registry(ctx, cfg)
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
        # the warm-up saved group by group, each with the sink's cursor of
        # its moment: the lowest is the first group's, and the lines the
        # later groups' histories earned lie past it. What the restarted
        # process writes lies past the sink as it finds it
        restart_at = os.path.getsize(alert_path)
        with ctx.span("generator_start"):
            env = dict(os.environ, PYTHONPATH=os.pathsep.join(
                [ctx.root, os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
            gen = subprocess.Popen(
                [sys.executable, "-m", "benchmark.generator_faults",
                 "--fields", str(F), "--null-share", str(traffic["null_share"]),
                 "--history", str(H), "--faults", json.dumps(traffic["faults"]),
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
        live.program = ResumedProgram(
            alert_path=alert_path,
            alert_flush_every=alerting["alert_flush_every"], latency=latency,
            resume_suppression=scan_alert_ids(alert_path, cursor))
        # the slot's records counted by the parser, polled beside the loop
        # against what the slot really offers (kind `live` counts S a slot)
        parsed_at = np.full(N, np.nan)
        want = np.cumsum(offered.sum(axis=1))

        def poll_parsed() -> None:
            k = 0
            while k < N and not poll_stop.is_set():
                if tcp.records_parsed >= want[k]:
                    parsed_at[k] = time.perf_counter()
                    k += 1
                else:
                    time.sleep(0.002)

        poller = threading.Thread(target=poll_parsed, daemon=True,
                                  name="benchmark-parsed-poll-offered")
        if ctx.trace:
            poller.start()
        record = live._serve(ctx, registry, tcp, gen, sent, phi, N)
        poll_stop.set()
        if poller.is_alive():
            poller.join(timeout=5)
        record.update(parsed_at=parsed_at, checkpoint_dir=ck_dir)
        if "alert_sink" in ctx.hooks:
            ctx.hooks["alert_sink"](alert_path, restart_at)  # tests: a lost line
        _account(ctx, record, setup_trace, latency, SimpleNamespace(
            past=past, past_raw=past_raw, past_ts=past_ts, offered=offered,
            phi=phi, ids=ids, drawn=drawn, alert_path=alert_path,
            cursor=cursor, restart_at=restart_at))
        return record
    finally:
        poll_stop.set()
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


def _account(ctx, record, setup_trace, latency, fleet) -> None:
    """kind `live`'s record made this deployment's: the set-up spans the
    program wrote, the records really offered, each sampled node's history
    before its served ticks, and the alert lines against the reference.
    `fleet`: what `run` made and learned of the fleet (its history, what it
    offered, its faults, the sink, the checkpoints' cursor, the sink's size
    at the restart)."""
    config, alerting = ctx.config, ctx.config["alerting"]
    cadence = ctx.traffic["cadence_s"]
    offered, phi, ids, drawn = fleet.offered, fleet.phi, fleet.ids, fleet.drawn
    (N, S), H = offered.shape, len(fleet.past_ts)

    # ---- the restart, from the program's own spans ----
    loads = [(setup_trace.epoch_perf + r["t0"], r["dur"])
             for r in setup_trace.records()
             if r["kind"] == "span" and r["name"] == "checkpoint_load"]
    emits = sorted((t0, t0 + d) for n, t0, d in record["host_spans"]
                   if n == "emit")
    record["host_spans"] += [("checkpoint_load", t0, d) for t0, d in loads]
    if loads and emits:
        # the first load begins -> the first tick the resumed loop emitted
        ctx.add_span("resume_first_tick", loads[0][0],
                     emits[0][1] - loads[0][0])

    # ---- a record a killed node never offered was not attempted ----
    scored = record["scored_tick"] >= 0
    if (scored & ~offered).any():
        record["rows_misrouted"] += int((scored & ~offered).sum())
    record["attempted"] = int(offered.sum())
    record["failed"] = int((offered & ~scored).sum())

    # ---- each sampled node's whole life, history first ----
    for s in record["sample"]:
        i = s["stream"]
        s["ts"] = np.concatenate([fleet.past_ts, s["ts"]])
        s["values"] = np.concatenate([fleet.past[:, i], s["values"]])
        s["raw"] = np.concatenate([fleet.past_raw[:, i], s["raw"]])

    # ---- the alert lines: every id once, none for a covered tick, and for
    # the sampled nodes exactly the lines the reference's rule says ----
    placed = sink_lines(fleet.alert_path)
    lines = [(node, tick) for _at, node, tick in placed]
    after = [(node, tick) for at, node, tick in placed
             if at >= fleet.restart_at]
    doubled = len(lines) - len(set(lines))
    covered = sum(1 for _node, tick in after if tick < H)
    lik_cfg = config["model"]["likelihood"]
    eps = config["precision"]["alert_epsilon"]
    written = set(lines)
    due_n = wrong = unjudged = 0
    for s in record["sample"]:
        loglik = ref_likelihood.log_likelihoods(s["raw"], lik_cfg)
        due, judged = ref_likelihood.judged_alerts(
            loglik, alerting["threshold"], alerting["debounce"], eps)
        got = np.array([(ids[s["stream"]], t) in written
                        for t in range(len(loglik))])
        due_n += int(due.sum())
        wrong += int((judged & (got != due)).sum())
        unjudged += int((~judged).sum())
    record["failed"] += wrong + doubled + covered
    stats = record["loop_stats"]
    record["alerts"] = {
        "lines": len(lines), "lines_after_restart": len(after),
        "doubled": doubled, "for_covered_ticks": covered,
        "sampled_due": due_n, "sampled_wrong": wrong,
        "sampled_unjudged": unjudged, "loop_count": stats.get("alerts")}
    node_of = {sid: i for i, sid in enumerate(ids)}
    faulted = {node: kind for node, kind, _a, _b in drawn}
    hit = {node_of[node] for node, _tick in after} & set(faulted)
    ctx.say(f"[live_resumed] alert lines in the sink {len(lines)} "
            f"({len(after)} after the restart at byte {fleet.restart_at}; "
            f"the checkpoints' lowest cursor {fleet.cursor}); doubled "
            f"ids {doubled}; lines for ticks the checkpoint covered "
            f"{covered}; sampled nodes: due {due_n}, decisions that differ "
            f"{wrong}, not judged (within {eps:g} of the threshold) "
            f"{unjudged}; faulted nodes {len(faulted)} "
            f"({sorted(set(faulted.values()))}), of them alerting after the "
            f"restart {len(hit)}; records offered {record['attempted']} of "
            f"{N * S} slots; failed {record['failed']}"
            + (f"; detect samples of the latency tracker "
               f"{latency.detect_samples}" if latency is not None else ""))

    # ---- due time -> the alert line durable, a line ----
    # the `alert` span of the tick that wrote a line ends after the sink's
    # flush; the line is about the record that tick scored
    alert_end = {}
    ticks = record["tick_spans"]
    for name, t0, dur in record["host_spans"]:
        if name == "alert":
            for k, (k0, kd) in ticks.items():
                if k0 <= t0 <= k0 + kd:
                    alert_end[k] = t0 + dur
    scored_tick, E = record["scored_tick"], record["E"]
    lat = []
    for node, tick in after:
        i, j = node_of[node], tick - H  # the loop's tick j scored group tick
        slot = np.nonzero(scored_tick[:, i] == j)[0]
        if len(slot) and j in alert_end:
            lat.append(alert_end[j] - (E + cadence * slot[0] + phi[i]))
    lat_ms = np.asarray(lat) * 1e3
    record["row_latency_ms"].update(
        alert_line_p50=float(np.percentile(lat_ms, 50)) if len(lat) else None,
        alert_line_p95=float(np.percentile(lat_ms, 95)) if len(lat) else None)
    if len(lat):
        ctx.say(f"[live_resumed] due time -> alert line durable, over "
                f"{len(lat)} lines about an offered record: p50 "
                f"{np.percentile(lat_ms, 50):.2f} p95 "
                f"{np.percentile(lat_ms, 95):.2f} ms")
