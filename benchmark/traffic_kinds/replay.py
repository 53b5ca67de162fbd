"""Traffic kind `replay`: the fleet's history at full rate.

Seeded chunks of `chunk_ticks` ticks go round-robin through EVERY resident
group with learning on, depth-2 pipelined (chunk i+1 is dispatched before
chunk i is collected), until `--seconds` have passed; every dispatched chunk
is collected before anything is counted. Rows per second is taken over the
time from the first dispatch to the last collect of those whole chunks."""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import program
from benchmark.feed import make_sine_feed, sample_streams, seed_key


class GroupFeed:
    """Random-access seeded chunks of one group's streams (lane = group)."""

    def __init__(self, seed: int, group: int, G: int, T: int):
        self.seed, self.lane, self.G, self.T = seed, group << 24, G, T
        _, _, self.phase = make_sine_feed(G, 1, seed_key(seed, self.lane))

    def values(self, c: int) -> np.ndarray:
        return make_sine_feed(self.G, self.T,
                              seed_key(self.seed, self.lane + 1 + c),
                              t0=c * self.T, phase=self.phase)[0]

    def ts(self, c: int) -> np.ndarray:
        t_idx = c * self.T + np.arange(self.T)[:, None]
        return (1_700_000_000 + t_idx + np.zeros((1, self.G))).astype(np.int64)


def run(ctx) -> dict:
    """One run of a replay cell (see benchmark/run.py for `ctx`)."""
    traffic, layout = ctx.traffic, ctx.config["layout"]
    T, depth = traffic["chunk_ticks"], traffic["pipeline_depth"]
    if depth != 2 or not traffic["learn"]:
        raise ValueError("the replay kind is depth-2, learning on")
    NG, G = layout["groups"], layout["group_size"]
    seed, seconds = ctx.seed, ctx.seconds

    with ctx.span("state"):
        cfg = program.model_config(ctx.config, control=ctx.control)
        groups = program.build_groups(cfg, NG, G, seed)
    feeds = [GroupFeed(seed, g, G, T) for g in range(NG)]
    # which of each group's streams `correct` follows
    picks = sample_streams(seed, NG * G, ctx.config["correct_sample_streams"])
    slots = {g: picks[picks // G == g] % G for g in range(NG)}
    served: dict[int, list] = {g: [] for g in range(NG)}  # raw[:, slots] per chunk

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

    def collect(pending) -> None:
        g, h, t_disp = pending
        t0 = time.perf_counter()
        program.wait_device(h)
        t1 = time.perf_counter()
        raw = groups[g].collect_chunk(h)[0]
        t2 = time.perf_counter()
        served[g].append(raw[:, slots[g]])
        host_s.append(t_disp + (t2 - t1))
        spans.append(("collect_wait", t0, t1 - t0))
        spans.append(("collect_host", t1, t2 - t1))

    trace_from = seconds - min(seconds, traffic["trace_window_s"])
    trace_sync = None
    ctx.compiles.start()
    ctx.setup_done()
    t_first = time.perf_counter()
    pending, i = None, 0
    while True:
        elapsed = time.perf_counter() - t_first
        if elapsed >= seconds:
            break
        if ctx.trace and trace_sync is None and elapsed >= trace_from:
            trace_sync = ctx.profiler_start()
        g, c = sequence(i)
        v = chunks.get((g, c))
        if v is None:
            v = feeds[g].values(c)
            generated_in_window += 1
        ts = feeds[g].ts(c)
        t0 = time.perf_counter()
        h = groups[g].dispatch_chunk(v, ts, learn=True)
        t1 = time.perf_counter()
        spans.append(("dispatch", t0, t1 - t0))
        if pending is not None:
            collect(pending)
        pending = (g, h, t1 - t0)
        i += 1
    if pending is not None:
        collect(pending)
    t_last = time.perf_counter()
    compiles = ctx.compiles.stop()
    if trace_sync is not None:
        ctx.profiler_stop(trace_sync, t_last)
    n_chunks = i
    rows = n_chunks * T * G
    ctx.say(f"[replay] {n_chunks} chunks of {T} ticks x {G} streams over "
            f"{NG} groups ({n_chunks / NG:.2f} rounds) in "
            f"{t_last - t_first:.3f}s; chunks generated inside the window "
            f"{generated_in_window}; compiles inside the window {compiles}")

    # ---- after the window: what `correct` compares ----
    sample = []
    for g in range(NG):
        if not len(slots[g]) or not served[g]:
            continue  # no sampled stream here, or a window too short to reach it
        raw = np.concatenate(served[g])  # [ticks, len(slots[g])]
        n_c = len(served[g])
        vals = np.concatenate([feeds[g].values(c) for c in range(n_c)])
        ts = np.concatenate([feeds[g].ts(c) for c in range(n_c)])
        for j, slot in enumerate(slots[g]):
            sample.append({
                "stream": g * G + int(slot), "seed": seed + g,
                "ts": ts[:, slot], "values": vals[:, slot], "raw": raw[:, j],
                **program.state_rows(groups[g], int(slot),
                                     ("perm", "syn_perm"))})
    stepped = sum(1 for g in range(NG) if served[g])
    return {
        "end_to_end": {"metrics_per_s": rows / (t_last - t_first)},
        "attempted": rows, "failed": 0,
        "window": (t_first, t_last), "rows_scored": rows,
        "streams": NG * G, "groups": NG, "groups_stepped": stepped,
        "chunk_ticks": T, "n_chunks": n_chunks,
        "host_s_per_chunk": host_s,
        "host_spans": spans, "compiles_in_window": compiles,
        "sample": sample, "tm_overflow": program.overflow_total(groups),
        "rows_misrouted": 0,
    }
