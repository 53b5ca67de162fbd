"""Measure the double-buffered feed gain on hardware (SURVEY.md §7 hard
part 3; round-2 verdict task 6's "measured overlap gain").

Compares, at steady state on the same StreamGroup:

- synchronous replay: run_chunk per chunk (device compute, then host
  likelihood, strictly alternating);
- pipelined replay: dispatch_chunk/collect_chunk depth-2 (host likelihood of
  chunk t overlaps device compute of chunk t+1 — utils/measure.py).

Prints one JSON line: {"sync": m/s, "pipelined": m/s, "gain": x}. The gain
is bounded by min(host, device) / max(host, device) overlap; with the host
likelihood measured ~250x faster than the device step (r3), expect a few
percent at most — the point is to MEASURE it, not assume it.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from rtap_tpu.utils.platform import enable_compile_cache, require_device  # noqa: E402

require_device()  # no TPU and no explicit CPU choice -> fail here


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--G", type=int, default=2048)
    ap.add_argument("--T", type=int, default=64)
    ap.add_argument("--chunks", type=int, default=4)
    args = ap.parse_args()

    enable_compile_cache()
    from rtap_tpu.config import cluster_preset
    from rtap_tpu.service.registry import StreamGroup
    from rtap_tpu.utils.measure import make_sine_feed, measure_pipelined

    G, T = args.G, args.T
    grp = StreamGroup(cluster_preset(), [f"p{i:05d}" for i in range(G)], backend="tpu")
    vals, ts, _ = make_sine_feed(G, T, key=(9, 9))
    grp.run_chunk(vals, ts)  # warmup/compile

    t0 = time.perf_counter()
    for i in range(args.chunks):
        grp.run_chunk(vals, ts + (i + 1) * T)
    sync = args.chunks * T * G / (time.perf_counter() - t0)

    pipelined, _ = measure_pipelined(grp, vals, ts + (args.chunks + 1) * T, args.chunks)

    print(json.dumps({
        "G": G, "T": T,
        "sync_metrics_per_s": round(sync, 1),
        "pipelined_metrics_per_s": round(pipelined, 1),
        "gain": round(pipelined / sync, 4),
    }), flush=True)


if __name__ == "__main__":
    main()
