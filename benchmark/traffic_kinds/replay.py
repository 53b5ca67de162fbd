"""Traffic kind `replay`: the fleet's history at full rate.

Seeded chunks of `chunk_ticks` ticks go round-robin through EVERY resident
group with learning on, each group `pipeline_depth` deep (its chunk i+1 is
dispatched before its chunk i is collected) and the groups' chunks queued
behind one another on the device, up to the mix's `dispatch_ahead_chunks`:
a host that stands still for less than that queue takes leaves the device
fed. When `--seconds` have passed nothing more is sent; every dispatched
chunk is collected before anything is counted. Rows per second is taken
over the time from the first dispatch to the last collect of those whole
chunks.

A configuration that states `correct_ticks` N (a whole multiple of
`chunk_ticks`) has its sampled streams compared over their first N ticks,
counted from the making of their state (warm-up chunk included), and their
permanences compared AT tick N: before a sampled group's chunk N/T is
dispatched the run drains what is in flight, reads the sampled slots' rows
and goes on. That pause is out of the clock (the window holds `--seconds`
of stepping, rows per second leaves `paused_s` out) and in the record (the
`correct_pause` host span, the `[replay]` line). Without the key every tick
the window held is followed and the state compared is the run's last."""

from __future__ import annotations

import math
import time
from collections import deque

import numpy as np

from benchmark import program
from benchmark.check import PERM_LEAVES
from benchmark.feed import make_sine_feed, sample_streams, seed_key


#: field f of a group draws from lane (group << 24) + f * _FIELD_LANE: above
#: every (group, chunk) lane of field 0, which is the one-field feed's own
_FIELD_LANE = 1 << 48


class GroupFeed:
    """Random-access seeded chunks of one group's streams: [T, G] for a
    one-field model, [T, G, n_fields] otherwise — each field a signal of its
    own (own lane, own per-stream phase), a pure function of seed, group,
    chunk and field."""

    def __init__(self, seed: int, group: int, G: int, T: int,
                 n_fields: int = 1):
        self.seed, self.G, self.T = seed, G, T
        self.lanes = [(group << 24) + f * _FIELD_LANE for f in range(n_fields)]
        self.phases = [make_sine_feed(G, 1, seed_key(seed, lane))[2]
                       for lane in self.lanes]

    def values(self, c: int) -> np.ndarray:
        fields = [make_sine_feed(self.G, self.T,
                                 seed_key(self.seed, lane + 1 + c),
                                 t0=c * self.T, phase=phase)[0]
                  for lane, phase in zip(self.lanes, self.phases)]
        return fields[0] if len(fields) == 1 else np.stack(fields, axis=-1)

    def ts(self, c: int) -> np.ndarray:
        t_idx = c * self.T + np.arange(self.T)[:, None]
        return (1_700_000_000 + t_idx + np.zeros((1, self.G))).astype(np.int64)


def in_flight_limit(traffic: dict, n_groups: int) -> int:
    """How many dispatched chunks the window holds at the most: each group
    `pipeline_depth` deep, over every resident group, and no more than the
    mix's `dispatch_ahead_chunks` (a mix without the key: two, one chunk
    ahead of the one waited for)."""
    depth = traffic["pipeline_depth"]
    return max(depth, min(depth * n_groups,
                          traffic.get("dispatch_ahead_chunks", depth)))


def trace_budget_s(traffic: dict, seconds: float, elapsed: float,
                   chunks: int, in_flight: int = 0) -> float:
    """How long before the window's close a `--trace 1` run starts the
    profiler: the mix's `trace_window_s`, or — where the mix states
    `trace_max_chunks` — the time that many chunks take at the rate the
    window has shown so far (`chunks` done in `elapsed`), if that is less;
    less the time the `in_flight` chunks take, which run inside the traced
    window too (it ends at the last collect). The reader parses ~2,900
    device events a chunk in Python, and a trace of a thousand chunks takes
    it longer than a run may last."""
    budget = min(seconds, traffic["trace_window_s"])
    if chunks:
        cap = traffic.get("trace_max_chunks")
        if cap:
            budget = min(budget, cap * elapsed / chunks)
        budget -= in_flight * elapsed / chunks
    return budget


def slowest_interval(spans: list) -> dict:
    """Where a window lost time, from its host spans: the longest stretch
    between two consecutive chunks' collects against the median one, and how
    much of it the host spent waiting for the device. A host that stopped
    (the device ran dry: the waits after it are short) and a device that ran
    slow (a long wait) both cost `metrics_per_s` their length; this tells
    them apart in the run's own `[replay]` line, with how many intervals ran
    over three medians and what they cost together beyond a median each."""
    waits = [(t0, d) for name, t0, d in spans if name == "collect_wait"]
    if len(waits) < 3:
        return {}
    ends = np.array([t0 + d for t0, d in waits])
    gaps = np.diff(ends)
    for name, t0, d in spans:
        if name == "correct_pause":  # off the clock, here too
            k = int(np.searchsorted(ends, t0, side="right")) - 1
            if 0 <= k < len(gaps):
                gaps[k] -= d
    k = int(gaps.argmax())
    median = float(np.median(gaps))
    long = gaps[gaps > 3 * median]
    return {"slowest_interval_s": float(gaps[k]),
            "median_interval_s": median,
            "slowest_interval_wait_s": float(waits[k + 1][1]),
            "long_intervals": int(long.size),
            "long_intervals_lost_s": float((long - median).sum())}


def run(ctx) -> dict:
    """One run of a replay cell (see benchmark/run.py for `ctx`)."""
    traffic, layout = ctx.traffic, ctx.config["layout"]
    T = traffic["chunk_ticks"]
    if traffic["pipeline_depth"] != 2 or not traffic["learn"]:
        raise ValueError("the replay kind is depth-2, learning on")
    NG, G = layout["groups"], layout["group_size"]
    limit = in_flight_limit(traffic, NG)
    seed, seconds = ctx.seed, ctx.seconds
    # chunks of each sampled stream that `correct` follows (None: all of them)
    follow = ctx.config.get("correct_ticks")
    if follow is not None:
        if follow <= 0 or follow % T:
            raise ValueError(f"correct_ticks {follow} is not a whole multiple "
                             f"of the traffic's chunk_ticks {T}")
        follow //= T

    with ctx.span("state"):
        cfg = program.model_config(ctx.config, control=ctx.control)
        groups = program.build_groups(cfg, NG, G, seed)
    feeds = [GroupFeed(seed, g, G, T, cfg.n_fields) for g in range(NG)]
    # which of each group's streams `correct` follows
    picks = sample_streams(seed, NG * G, ctx.config["correct_sample_streams"])
    slots = {g: picks[picks // G == g] % G for g in range(NG)}
    served: dict[int, list] = {g: [] for g in range(NG)}  # raw[:, slots] per chunk
    state_at: dict[int, list] = {}  # group -> its sampled slots' rows at tick N

    def sequence(i: int) -> tuple[int, int]:
        """Window chunk i -> (group, that group's chunk index). Group 0's
        chunk 0 is the warm-up, so the window starts at group 1."""
        return (i + 1) % NG, (i + 1) // NG

    with ctx.span("pregenerate"):
        n_pre = math.ceil(seconds * traffic["pregenerate_rows_per_s"]
                          / (T * G))
        chunks = {(g, c): feeds[g].values(c)
                  for g, c in map(sequence, range(n_pre))}
    generated_in_window = 0

    with ctx.span("warm_compile"):
        # the cell's one program (chunk_step at [T, G]) through the timed
        # path's own entry, on group 0's first chunk
        h = groups[0].dispatch_chunk(feeds[0].values(0), feeds[0].ts(0),
                                     learn=True)
        program.wait_device(h)
        served[0].append(groups[0].collect_chunk(h)[0][:, slots[0]])

    host_s: list[float] = []  # per chunk: dispatch + collect outside the wait
    spans: list[tuple[str, float, float]] = []  # (name, t0, dur) of the window

    pending: deque = deque()  # (group, handle, dispatch time), oldest first

    def collect() -> None:
        g, h, t_disp = pending.popleft()
        t0 = time.perf_counter()
        program.wait_device(h)
        t1 = time.perf_counter()
        raw = groups[g].collect_chunk(h)[0]
        t2 = time.perf_counter()
        served[g].append(raw[:, slots[g]])
        host_s.append(t_disp + (t2 - t1))
        spans.append(("collect_wait", t0, t1 - t0))
        spans.append(("collect_host", t1, t2 - t1))

    def read_state(g: int) -> list:
        return [program.state_rows(groups[g], int(slot), PERM_LEAVES)
                for slot in slots[g]]

    trace_sync = None
    ctx.compiles.start()
    ctx.setup_done()
    t_first = time.perf_counter()
    i, paused_s = 0, 0.0
    while True:
        elapsed = time.perf_counter() - t_first - paused_s
        if elapsed >= seconds:
            break  # time is up: nothing more is sent
        if ctx.trace and trace_sync is None and seconds - elapsed <= \
                trace_budget_s(traffic, seconds, elapsed, i - len(pending),
                               len(pending)):
            trace_sync = ctx.profiler_start()
        g, c = sequence(i)
        if c == follow and len(slots[g]) and g not in state_at:
            # group g is at tick N once what is in flight has landed: its
            # state is read now, off the clock, before chunk N/T moves it on
            while pending:
                collect()
            t0 = time.perf_counter()
            ctx.compiles.stop()  # the rows' slices are no part of the window
            state_at[g] = read_state(g)
            ctx.compiles.start()
            dt = time.perf_counter() - t0
            paused_s += dt
            spans.append(("correct_pause", t0, dt))
        v = chunks.get((g, c))
        if v is None:
            v = feeds[g].values(c)
            generated_in_window += 1
        ts = feeds[g].ts(c)
        t0 = time.perf_counter()
        h = groups[g].dispatch_chunk(v, ts, learn=True)
        t1 = time.perf_counter()
        spans.append(("dispatch", t0, t1 - t0))
        pending.append((g, h, t1 - t0))
        i += 1
        while len(pending) >= limit:
            collect()
    while pending:  # all that was sent counts, over all the time it took
        collect()
    t_last = time.perf_counter()
    compiles = ctx.compiles.stop()
    if trace_sync is not None:
        ctx.profiler_stop(trace_sync, t_last)
    n_chunks = i
    rows = n_chunks * T * G
    stepping_s = t_last - t_first - paused_s
    slowest = slowest_interval(spans)
    ctx.say(f"[replay] {n_chunks} chunks of {T} ticks x {G} streams over "
            f"{NG} groups ({n_chunks / NG:.2f} rounds), at most {limit} "
            f"dispatched at a time, in {stepping_s:.3f}s of stepping"
            + (f" + {paused_s:.3f}s paused, off the clock, to read "
               f"{len(state_at)} sampled group(s)' state at tick {follow * T}"
               if follow else "")
            + f"; chunks generated inside the window {generated_in_window}; "
            f"compiles inside the window {compiles}"
            + ("; slowest chunk-to-chunk interval {slowest_interval_s:.4f}s "
               "(median {median_interval_s:.4f}s), {slowest_interval_wait_s:.4f}s "
               "of it waiting for the device; {long_intervals} interval(s) "
               "over three medians cost {long_intervals_lost_s:.4f}s"
               .format(**slowest)
               if slowest else ""))

    # ---- after the window: what `correct` compares ----
    sample = []
    for g in range(NG):
        if not len(slots[g]) or not served[g]:
            continue  # no sampled stream here, or a window too short to reach it
        # a group the window left short of tick N is followed as far as it
        # got, against the state it ended in (read_state now IS that tick's)
        n_c = len(served[g]) if follow is None else min(follow, len(served[g]))
        raw = np.concatenate(served[g][:n_c])  # [ticks, len(slots[g])]
        vals = np.concatenate([feeds[g].values(c) for c in range(n_c)])
        ts = np.concatenate([feeds[g].ts(c) for c in range(n_c)])
        rows_at = state_at[g] if g in state_at else read_state(g)
        for j, slot in enumerate(slots[g]):
            sample.append({
                "stream": g * G + int(slot), "seed": seed + g,
                "ts": ts[:, slot], "values": vals[:, slot], "raw": raw[:, j],
                **rows_at[j]})
    stepped = sum(1 for g in range(NG) if served[g])
    return {
        "end_to_end": {"metrics_per_s": rows / stepping_s},
        "attempted": rows, "failed": 0,
        "window": (t_first, t_last), "paused_s": paused_s, **slowest,
        "rows_scored": rows,
        "streams": NG * G, "groups": NG, "groups_stepped": stepped,
        "chunk_ticks": T, "n_chunks": n_chunks,
        "host_s_per_chunk": host_s,
        "host_spans": spans, "compiles_in_window": compiles,
        "sample": sample, "tm_overflow": program.overflow_total(groups),
        "tm_capacity": program.capacity_total(groups),
        "rows_misrouted": 0,
    }
