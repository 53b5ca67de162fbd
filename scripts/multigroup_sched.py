"""Multi-group-per-chip scheduling experiment (round-3 verdict, weak #3/#9).

The measured G-sweep says throughput per chip FALLS as one vmapped group
grows (38,956 metrics/s @ G=256 vs 29,725 @ G=8192 — SCALING.md): nothing
amortizes across streams, so a giant group only adds XLA workspace pressure.
The service story has therefore been "run many small groups" — asserted,
never measured. This script measures it: fixed TOTAL streams, split into k
equal groups, steady-state scored-metrics/s under two schedules:

- sequential: each group replays its whole span before the next starts
  (the current replay_streams shape), depth-2 pipelined within a group;
- interleaved: round-robin chunk dispatch across all k groups — every
  group keeps one chunk in flight, so the host's likelihood post-process
  for group A overlaps device compute for group B *and* the device queue
  never drains between groups.

All k groups share one compiled program (same shapes -> one jit cache
entry), so k only costs HBM state, not compile time. Output: one table +
reports/multigroup_sched.json for SCALING.md.

Usage: python scripts/multigroup_sched.py [--total 2048] [--splits 1,2,4,8]
       [--chunk-ticks 64] [--measure-chunks 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from rtap_tpu.utils.platform import enable_compile_cache, require_device  # noqa: E402

require_device()  # no TPU and no explicit CPU choice -> fail here
enable_compile_cache()

import numpy as np  # noqa: E402

from rtap_tpu.config import cluster_preset  # noqa: E402
from rtap_tpu.service.registry import StreamGroup  # noqa: E402
from rtap_tpu.utils.measure import make_sine_feed  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _make_chunks(G: int, T: int, n_chunks: int, seed: int):
    """Pre-generate n_chunks of fresh (phase-continuing) values outside the
    timed window — novelty keeps the learning path honest (r3 weak #8)."""
    vals, ts, phase = make_sine_feed(G, T, key=(seed, 11))
    chunks = [(vals, ts)]
    for i in range(1, n_chunks):
        v, t, _ = make_sine_feed(G, T, key=(seed, 11 + i), t0=i * T, phase=phase)
        chunks.append((v, t))
    return chunks


def run_config(total: int, k: int, chunk_ticks: int, measure_chunks: int,
               backend: str) -> dict:
    G = total // k
    cfg = cluster_preset()
    log(f"-- {k} group(s) x G={G} (total {total}) --")
    t0 = time.perf_counter()
    groups = [
        StreamGroup(cfg, [f"s{g}_{i}" for i in range(G)], seed=g, backend=backend)
        for g in range(k)
    ]
    init_s = time.perf_counter() - t0
    # per-group chunk feeds: warmup chunk + measured chunks, distinct noise
    feeds = [_make_chunks(G, chunk_ticks, 1 + measure_chunks, seed=100 + g)
             for g in range(k)]

    # warmup: compile (shared across groups — same shapes) + 1st chunk each
    t0 = time.perf_counter()
    for g, grp in enumerate(groups):
        grp.collect_chunk(grp.dispatch_chunk(*feeds[g][0]))
    warm_s = time.perf_counter() - t0

    # sequential schedule: group-at-a-time, depth-2 within the group
    t0 = time.perf_counter()
    for g, grp in enumerate(groups):
        pending = grp.dispatch_chunk(*feeds[g][1])
        for i in range(2, 1 + measure_chunks):
            nxt = grp.dispatch_chunk(*feeds[g][i])
            grp.collect_chunk(pending)
            pending = nxt
        grp.collect_chunk(pending)
    seq_dt = time.perf_counter() - t0
    seq_rate = measure_chunks * chunk_ticks * total / seq_dt

    # fresh chunks for the interleaved pass (state has advanced; novelty again)
    feeds = [_make_chunks(G, chunk_ticks, measure_chunks, seed=500 + g)
             for g in range(k)]
    # interleaved schedule: round-robin dispatch, collect one round behind
    t0 = time.perf_counter()
    pending = [grp.dispatch_chunk(*feeds[g][0]) for g, grp in enumerate(groups)]
    for i in range(1, measure_chunks):
        nxt = [grp.dispatch_chunk(*feeds[g][i]) for g, grp in enumerate(groups)]
        for g, grp in enumerate(groups):
            grp.collect_chunk(pending[g])
        pending = nxt
    for g, grp in enumerate(groups):
        grp.collect_chunk(pending[g])
    inter_dt = time.perf_counter() - t0
    inter_rate = measure_chunks * chunk_ticks * total / inter_dt

    row = {
        "k_groups": k, "G": G, "total": total,
        "init_s": round(init_s, 2), "warmup_s": round(warm_s, 2),
        "sequential_metrics_per_s": round(seq_rate, 1),
        "interleaved_metrics_per_s": round(inter_rate, 1),
        "interleave_gain": round(inter_rate / seq_rate, 3),
    }
    log(json.dumps(row))
    del groups  # free HBM before the next configuration
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--total", type=int, default=2048)
    ap.add_argument("--splits", default="1,2,4,8")
    ap.add_argument("--chunk-ticks", type=int, default=64)
    ap.add_argument("--measure-chunks", type=int, default=4)
    ap.add_argument("--backend", default="tpu")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "reports", "multigroup_sched.json"))
    args = ap.parse_args()

    splits = [int(s) for s in args.splits.split(",")]
    bad = [k for k in splits if args.total % k]
    if bad:
        raise SystemExit(f"--total {args.total} not divisible by splits {bad}")

    rows = [run_config(args.total, k, args.chunk_ticks, args.measure_chunks,
                       args.backend) for k in splits]
    import jax

    result = {
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "total_streams": args.total,
        "chunk_ticks": args.chunk_ticks,
        "measure_chunks": args.measure_chunks,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
