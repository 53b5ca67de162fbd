"""Traffic kind `live`: the product path, phase-locked and drained.

serve's own pieces — TcpJsonlSource (native parser), a finalized registry,
live_loop with its AOT warm-up — run in this process; the rows come from a
generator process of its own (benchmark/generator.py) over localhost TCP.

The offered set is a pure function of the cell's files, --seed and
--seconds: N = floor(seconds / cadence) slots, one row per stream per slot,
stream i's row of slot k due at E + k*cadence + phi[i], phi in
[0, phase_spread_s]. The phase is taken FROM the loop: its tick 0 is a
priming tick (set-up, outside the offered set) whose snapshot instant S0
fixes E = S0 + guard_s, so the loop's tick k+1 snapshots slot k at
E + k*cadence + (cadence - guard_s): guard_s after the slot's last due row,
guard_s before the next slot's first. After slot N-1 the loop runs on (at
most `drain_cadences` more ticks) until every offered row has been seen or
overwritten, and only then is anything counted. A row is failed iff no tick
scored it."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import program
from benchmark.feed import live_rows, sample_streams


class SnapshotRecorder:
    """Wraps the loop's `source` callable: records every snapshot (instant,
    values, ts), fixes E at the priming tick and hands it to the generator,
    matches each snapshot's values to the offered rows, and ends the loop
    once nothing offered is outstanding."""

    def __init__(self, inner, sent: np.ndarray, n_slots: int, guard_s: float,
                 on_prime, on_snapshot, stop_event, stall=None):
        self.inner, self.sent, self.N = inner, sent, n_slots
        self.guard_s, self.on_prime, self.stop = guard_s, on_prime, stop_event
        self.on_snapshot = on_snapshot
        self.stall = stall or {}
        S = sent.shape[1]
        self.snap_t: list[float] = []
        self.snap_ts: list[int] = []
        self.snap_values: list[np.ndarray] = []
        self.next_slot = np.zeros(S, np.int64)  # first slot not yet accounted
        self.scored_tick = np.full((n_slots, S), -1, np.int64)
        self.misrouted = 0
        self.tick = -1
        self.E = None

    def __call__(self, tick: int):
        if tick in self.stall:
            time.sleep(self.stall[tick])  # tests: a stalled poll
        values, ts = self.inner(tick)
        t = time.perf_counter()
        self.tick = tick
        values = np.asarray(values, np.float32)
        self.snap_t.append(t)
        self.snap_ts.append(int(ts))
        self.snap_values.append(values.copy())
        if tick == 0:
            self.E = t + self.guard_s
            self.on_prime(self.E)
        self.on_snapshot(t)
        if tick == 0:
            finite = np.isfinite(values)
            self.misrouted += int(finite.sum())  # nothing was offered yet
            return values, ts
        self._match(tick, values)
        if tick >= self.N and int(self.next_slot.min(initial=self.N)) >= self.N:
            self.stop.set()  # every offered row is accounted for
        return values, ts

    def _match(self, tick: int, values: np.ndarray) -> None:
        """A finite value is the offered row of the earliest slot not yet
        accounted for that carries exactly it; slots skipped on the way were
        overwritten before any snapshot saw them."""
        finite = np.isfinite(values)
        if not finite.any():
            return
        k_idx = np.arange(self.N)[:, None]
        hit = (self.sent == values[None, :]) & (k_idx >= self.next_slot[None, :])
        found = hit.any(axis=0)
        slot = hit.argmax(axis=0)
        ok = finite & found
        cols = np.nonzero(ok)[0]
        self.scored_tick[slot[cols], cols] = tick
        self.next_slot[cols] = slot[cols] + 1
        self.misrouted += int((finite & ~found).sum())


def run(ctx) -> dict:
    traffic, layout = ctx.traffic, ctx.config["layout"]
    cadence, guard = traffic["cadence_s"], traffic["guard_s"]
    if ctx.config.get("live_cadence_s") != cadence:
        raise ValueError(
            f"traffic {traffic['name']!r} runs at {cadence} s; configuration "
            f"{ctx.config['name']!r} states live_cadence_s "
            f"{ctx.config.get('live_cadence_s')!r}")
    if traffic["phase_spread_s"] + 2 * guard > cadence + 1e-9:
        raise ValueError("phase_spread_s + 2 * guard_s must fit in a cadence")
    NG, G = layout["groups"], layout["group_size"]
    S = NG * G
    N = int(ctx.seconds // cadence)
    if N < 1:
        raise ValueError(f"--seconds {ctx.seconds} holds no {cadence} s slot")
    seed = ctx.seed
    sent, phi, _send = live_rows(seed, S, N, traffic["phase_spread_s"],
                                    traffic["send_quantum_s"])

    with ctx.span("state"):
        cfg = program.model_config(ctx.config, control=ctx.control)
        registry, ids = program.build_registry(cfg, NG, G, seed)
        tcp = program.tcp_source(ids, require_native=not ctx.allow_cpu)
    gen = None
    try:
        with ctx.span("generator_start"):
            env = dict(os.environ, PYTHONPATH=os.pathsep.join(
                [ctx.root, os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
            gen = subprocess.Popen(
                [sys.executable, "-m", "benchmark.generator",
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
        return _serve(ctx, registry, tcp, gen, sent, phi, N)
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
        tcp.close()


def _serve(ctx, registry, tcp, gen, sent, phi, N) -> dict:
    traffic = ctx.traffic
    cadence, guard = traffic["cadence_s"], traffic["guard_s"]
    groups = registry.groups
    NG, G = len(groups), groups[0].G
    S = NG * G
    seed = ctx.seed
    trace = program.trace_recorder()
    stop = threading.Event()

    # what the timed path served, for `correct`: the sampled streams' raw
    # score of every tick, taken where collect_chunk hands it to the loop
    picks = sample_streams(seed, S, ctx.config["correct_sample_streams"])
    slots = {g: picks[picks // G == g] % G for g in range(NG)}
    served: dict[int, list] = {g: [] for g in range(NG)}
    collected_ticks: dict[int, list] = {g: [] for g in range(NG)}

    def wrap(g: int, grp):
        inner = grp.collect_chunk

        def collect_chunk(handle):
            out = inner(handle)
            served[g].append(out[0][:, slots[g]].copy())
            collected_ticks[g].append(recorder.tick)
            return out

        grp.collect_chunk = collect_chunk

    prof = {"sync": None, "timer": None}
    trace_ticks = max(1, min(N, int(traffic["trace_window_s"] // cadence)))

    def on_prime(E: float) -> None:
        gen.stdin.write(f"E {E!r}\n")
        gen.stdin.flush()
        s0 = E - guard
        # the AOT warm-up is over: from here on nothing may compile
        ctx.compiles.start()
        if ctx.trace:
            # profile the last trace_ticks cadences, started in the sleep
            # before the first of them
            t_on = s0 + cadence * (1 + N - trace_ticks) - 0.5
            prof["timer"] = threading.Timer(
                max(0.0, t_on - time.perf_counter()),
                lambda: prof.update(sync=ctx.profiler_start()))
            prof["timer"].start()

    def on_snapshot(t: float) -> None:
        try:
            gen.stdin.write(f"S {t!r}\n")
            gen.stdin.flush()
        except (BrokenPipeError, ValueError):
            pass  # the generator is gone; its missing report ends the run

    recorder = SnapshotRecorder(tcp, sent, N, guard, on_prime, on_snapshot,
                                stop, stall=ctx.hooks.get("stall"))
    for g, grp in enumerate(groups):
        wrap(g, grp)

    # ingest lag (traced runs only): when records_parsed reaches each slot's
    # cumulative row count, polled beside the loop
    parsed_at = np.full(N, np.nan)
    poll_stop = threading.Event()

    def poll_parsed() -> None:
        k = 0
        while k < N and not poll_stop.is_set():
            if tcp.records_parsed >= (k + 1) * S:
                parsed_at[k] = time.perf_counter()
                k += 1
            else:
                time.sleep(0.002)

    poller = threading.Thread(target=poll_parsed, daemon=True,
                              name="benchmark-parsed-poll")
    if ctx.trace:
        poller.start()

    t_loop = time.perf_counter()
    try:
        stats = program.live_loop(
            recorder, registry, N + 1 + traffic["drain_cadences"], cadence,
            traffic, trace, stop)
    finally:
        t_end = time.perf_counter()
        compiles = ctx.compiles.stop()
        if prof["timer"] is not None:
            prof["timer"].cancel()
        poll_stop.set()
        if poller.is_alive():
            poller.join(timeout=5)
        if prof["sync"] is not None:
            ctx.profiler_stop(prof["sync"], t_end)
    # the window opens with the loop's tick 1, one cadence after the
    # priming snapshot
    t_window = recorder.E - guard + cadence
    ctx.setup_done(at=t_window)
    report = json.loads(gen.stdout.readline() or "{}")
    if "error" in report or "rows_sent" not in report:
        raise RuntimeError(f"generator: {report}")

    # ---- the loop's own spans, on perf_counter ----
    recs = trace.records()
    epoch = trace.epoch_perf

    def loop_spans(name: str) -> dict[int, tuple[float, float]]:
        return {r["tick"]: (epoch + r["t0"], r["dur"]) for r in recs
                if r["kind"] == "span" and r["name"] == name
                and r["group"] < 0}

    tick_sp, emit_sp = loop_spans("tick"), loop_spans("emit")
    coll_sp = loop_spans("collect")
    ticks_run = int(stats["ticks"])
    E = recorder.E
    snaps = np.array(recorder.snap_t)

    # ---- accounting: which tick scored each offered row ----
    scored_tick = recorder.scored_tick.copy()
    for g in range(NG):
        # a group that did not collect a tick (quarantined) scored nothing
        # in it
        missing = set(range(ticks_run)) - set(collected_ticks[g])
        if missing:
            block = scored_tick[:, g * G:(g + 1) * G]
            block[np.isin(block, sorted(missing))] = -1
    scored = scored_tick >= 0
    emit_end = np.full(ticks_run + 1, np.nan)
    for k, (t0, dur) in emit_sp.items():
        if k <= ticks_run:
            emit_end[k] = t0 + dur
    due = E + cadence * np.arange(N)[:, None] + phi[None, :]
    latency = np.where(scored, emit_end[np.clip(scored_tick, 0, ticks_run)]
                       - due, np.nan)
    scored &= np.isfinite(latency)
    attempted = N * S
    failed = attempted - int(scored.sum())
    in_order = bool((scored_tick[scored] >= 1).all())

    # ---- the guard actually observed ----
    sent_first = np.array(report["first_send"], float)  # None -> nan: unsent
    sent_last = np.array(report["last_send"], float)
    n_seen = min(len(snaps) - 1, N)
    before = snaps[1:n_seen + 1] - sent_last[:n_seen]
    after = sent_first[1:n_seen] - snaps[1:n_seen]
    guard_before = float(before.min()) if len(before) else float("nan")
    guard_after = float(after.min()) if len(after) else float("nan")
    # how far each snapshot fell from the instant the schedule counts on
    slip = snaps - (snaps[0] + cadence * np.arange(len(snaps)))
    if (failed or min(guard_before, guard_after) < guard / 2
            or np.abs(slip).max() > guard / 2):
        lost = (~scored).sum(axis=1)
        for j in range(len(snaps)):
            t0, dur = tick_sp.get(j, (np.nan, np.nan))
            k = j - 1  # the slot this tick should snapshot
            ctx.say(
                f"[live]   tick {j}: snapshot slip {slip[j]:+.3f} s, tick "
                f"{dur:.3f} s, collect {coll_sp.get(j, (0, np.nan))[1]:.3f} s"
                + (f"; slot {k}: first send E+{sent_first[k] - E:.3f}, last "
                   f"send E+{sent_last[k] - E:.3f}, unscored {int(lost[k])}"
                   if 0 <= k < N else ""))
    ctx.say(f"[live] slots {N} x {S} streams = attempted {attempted}; failed "
            f"{failed}; ticks run {ticks_run} (1 priming + {N} + "
            f"{ticks_run - 1 - N} drain); E = priming snapshot + {guard} s; "
            f"snapshot guard observed: snapshot - last arrival min "
            f"{guard_before:.3f} s, next arrival - snapshot min "
            f"{guard_after:.3f} s; snapshot slip max {np.abs(slip).max():.3f} s; "
            f"generator late max {report['late_ms_max']:.1f} ms, batches held "
            f"for a snapshot {report['batches_held']}; generator rows "
            f"sent {report['rows_sent']}, "
            f"records_parsed {tcp.records_parsed}, parse errors "
            f"{tcp.parse_errors}; missed deadlines "
            f"{stats.get('missed_deadlines')}; compiles inside the window "
            f"{compiles} (loop's own count after warm-up "
            f"{stats.get('cold_compiles_after_warmup')})")
    if tcp.records_parsed != report["rows_sent"] or tcp.parse_errors:
        recorder.misrouted += abs(tcp.records_parsed - report["rows_sent"]) \
            + tcp.parse_errors
    if not in_order:
        recorder.misrouted += 1

    # ---- per-row latencies, from the due time and from the snapshot ----
    window_ticks = [k for k in range(1, ticks_run) if k in tick_sp]
    lat_ms = latency[scored] * 1e3
    # the same rows' latency counted from the snapshot that took them: what
    # the program adds once it holds a row, free of the loop's phase
    tick_of = np.clip(scored_tick, 0, min(ticks_run, len(snaps) - 1))
    score_ms = (emit_end[tick_of] - snaps[tick_of])[scored] * 1e3

    def pct(a, q):
        return float(np.percentile(a, q)) if len(a) else float("nan")

    service = [(emit_end[k] - snaps[k]) * 1e3 for k in window_ticks
               if k < len(snaps)]
    ctx.say("[live] per tick, snapshot -> emitted ms: "
            + " ".join(f"{v:.1f}" for v in service)
            + "; snapshot slip ms: "
            + " ".join(f"{v * 1e3:+.1f}" for v in slip[1:ticks_run])
            + f"; score p50 {pct(score_ms, 50):.2f} p95 {pct(score_ms, 95):.2f}"
            f", detect (from the due time) p50 {pct(lat_ms, 50):.2f} p95 "
            f"{pct(lat_ms, 95):.2f} ms")

    # ---- what `correct` compares: every tick each sampled stream was fed ----
    sample = []
    vals_by_tick = np.stack(recorder.snap_values[:ticks_run])  # [ticks, S]
    # the loop clamps source timestamps monotonic before the models see them
    ts_fed = np.maximum.accumulate(np.array(recorder.snap_ts[:ticks_run]))
    for g in range(NG):
        if not len(slots[g]) or len(served[g]) != ticks_run:
            if len(slots[g]):
                recorder.misrouted += 1  # a sampled group lost ticks
            continue
        raw = np.concatenate(served[g])
        for j, slot in enumerate(slots[g]):
            sample.append({
                # the registry seeds group g's state with seed + g
                "stream": g * G + int(slot), "seed": seed + g, "ts": ts_fed,
                "values": vals_by_tick[:, g * G + int(slot)],
                "raw": raw[:, j],
                **program.state_rows(groups[g], int(slot),
                                     ("perm", "syn_perm"))})

    ctx.add_span("warm_compile", t_loop,
                 (tick_sp[0][0] if 0 in tick_sp else t_loop) - t_loop)
    return {
        "end_to_end": {"score_p50_ms": pct(score_ms, 50)},
        "row_latency_ms": {
            "detect_p50": pct(lat_ms, 50), "detect_p95": pct(lat_ms, 95),
            "score_p95": pct(score_ms, 95)},
        "attempted": attempted, "failed": failed,
        "window": (t_window, t_end),
        "streams": S, "groups": NG, "groups_stepped": NG, "slots": N,
        "ticks_run": ticks_run, "chunk_ticks": traffic["micro_chunk"],
        "loop_stats": stats,
        "tick_spans": {k: tick_sp[k] for k in window_ticks},
        "collect_spans": {k: coll_sp[k] for k in window_ticks if k in coll_sp},
        "host_spans": _host_spans(recs, epoch, tick_sp, cadence),
        "generator": report, "parsed_at": parsed_at,
        "snap_t": snaps, "E": E, "scored_tick": np.where(scored, scored_tick, -1),
        "guard_before_s": guard_before, "guard_after_s": guard_after,
        "compiles_in_window": compiles,
        "sample": sample, "tm_overflow": program.overflow_total(groups),
        "rows_misrouted": recorder.misrouted,
    }


def _host_spans(recs, epoch, tick_sp, cadence):
    """(name, t0, dur) on perf_counter: the loop's phases plus the sleep
    between one tick's end and the next tick's start."""
    spans = [(r["name"], epoch + r["t0"], r["dur"]) for r in recs
             if r["kind"] == "span" and r["group"] < 0 and r["name"] != "tick"]
    ticks = sorted(tick_sp)
    for a, b in zip(ticks, ticks[1:]):
        end = tick_sp[a][0] + tick_sp[a][1]
        spans.append(("cadence_sleep", end, max(0.0, tick_sp[b][0] - end)))
    return spans
