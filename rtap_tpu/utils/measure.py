"""Shared benchmark-measurement building blocks.

scripts/scaling_law.py (the G-sweep), __graft_entry__ (the multi-chip dry
run), chip_smoke.py and the soak scripts all drive the same workload shape:
a synthetic diurnal cluster feed through the depth-2 pipelined chunk replay.
One implementation here, so a change to the feed or the measurement window
can never make two of them measure different things.
"""

from __future__ import annotations

import time

import numpy as np


def make_sine_feed(
    G: int, chunk_ticks: int, key: tuple[int, int], t0: int = 0,
    phase: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Diurnal sine + Gaussian noise for G streams over one chunk.

    -> (values [T, G] f32, ts [T, G] i64, phase [G]) — pass `phase` back in
    to generate consecutive chunks of the same streams.
    """
    rng = np.random.Generator(np.random.Philox(key=key))
    if phase is None:
        phase = rng.integers(0, 86400, G)
    t_idx = t0 + np.arange(chunk_ticks)[:, None]
    base = 35.0 + 20.0 * np.sin(2 * np.pi * (t_idx + phase[None, :]) / 86400.0)
    vals = (base + rng.normal(0, 3.0, (chunk_ticks, G))).astype(np.float32)
    ts = (1_700_000_000 + t_idx + np.zeros((1, G))).astype(np.int64)
    return vals, ts, phase


def measure_pipelined(
    grp, vals: np.ndarray, ts: np.ndarray, measure_chunks: int = 3,
    novel: tuple[tuple[int, int], np.ndarray] | None = None,
):
    """Steady-state scored-metrics/s over `measure_chunks` chunk dispatches,
    overlapped depth-2 (dispatch chunk i+1 before collecting chunk i —
    SURVEY.md §7 hard part 3). The group must already be warmed up (compiled).

    `novel=(key, phase)`: each measured chunk carries FRESH values continuing
    `vals`' streams via the phase-advancing feed (per-chunk noise key), so
    steady state includes genuine novelty and the learning path's real cost —
    re-dispatching one chunk lets the TM fully learn a T-tick loop and
    flatters throughput (round-3 verdict, weak #8). Chunks are pre-generated
    OUTSIDE the timed window (the live service overlaps ingest with device
    compute; host rng is not the thing under measurement). Default (None)
    keeps the old re-dispatch behavior for A/B comparability.
    """
    chunk_ticks, G = vals.shape[:2]
    if novel is not None:
        key, phase = novel
        chunks = []
        for i in range(measure_chunks):
            v, t, _ = make_sine_feed(
                G, chunk_ticks, key=(key[0], key[1] + 1 + i),
                t0=(i + 1) * chunk_ticks, phase=phase,
            )
            chunks.append((v, t))
    else:
        chunks = [(vals, ts + (i + 1) * chunk_ticks) for i in range(measure_chunks)]
    t0 = time.perf_counter()
    pending = grp.dispatch_chunk(*chunks[0])
    for i in range(1, measure_chunks):
        nxt = grp.dispatch_chunk(*chunks[i])
        grp.collect_chunk(pending)
        pending = nxt
    grp.collect_chunk(pending)
    dt = time.perf_counter() - t0
    return measure_chunks * chunk_ticks * G / dt, dt
