"""100k-stream sharded execution proof on a virtual 8-device mesh.

SURVEY.md config 5 / round-2 verdict task 2: demonstrate the NORTH-STAR
stream count actually executing through the production sharded path
(`sharded_chunk_step`, explicit shard_map SPMD, zero collectives) — on this
host via `--xla_force_host_platform_device_count`, since real multi-chip
hardware is not reachable from this environment. This validates shapes,
sharding layouts, HBM-scale state construction (~54 GiB at u16), and the
donation path at full scale; per-chip throughput comes from the benchmark
(`python -m benchmark.run`) on real silicon.

    python scripts/virtual_mesh_run.py [--streams 100000] [--devices 8]
                                       [--ticks 2] [--perm-bits 16]

Prints one JSON line with wall times and per-stream bytes.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--streams", type=int, default=100_000)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=2)
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--perm-bits", type=int, default=16, choices=(0, 8, 16))
    args = ap.parse_args()

    from rtap_tpu.utils.platform import enable_compile_cache, force_virtual_devices

    force_virtual_devices(args.devices)
    enable_compile_cache()
    import jax

    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.models.state import init_state, state_nbytes
    from rtap_tpu.ops.step import sharded_chunk_step
    from rtap_tpu.parallel import make_stream_mesh
    from rtap_tpu.parallel.sharding import broadcast_group_state
    from rtap_tpu.utils.measure import make_sine_feed

    cfg = cluster_preset(perm_bits=args.perm_bits)
    G, T = args.streams, args.ticks
    per = state_nbytes(cfg)["total"]
    print(f"state: {per} B/stream x {G} = {per * G / 1024**3:.1f} GiB",
          file=sys.stderr, flush=True)

    mesh = make_stream_mesh(args.devices)
    t0 = time.perf_counter()
    state = broadcast_group_state(init_state(cfg, seed=0), G, mesh)
    jax.block_until_ready(state["syn_perm"])
    t_init = time.perf_counter() - t0
    print(f"state build+shard: {t_init:.1f}s", file=sys.stderr, flush=True)

    from jax.sharding import NamedSharding, PartitionSpec as P

    phase = None
    walls = []
    for c in range(args.chunks):
        vals, ts, phase = make_sine_feed(G, T, key=(13, 1), t0=c * T, phase=phase)
        vals_d = jax.device_put(vals[..., None], NamedSharding(mesh, P(None, "streams", None)))
        ts_d = jax.device_put(ts.astype(np.int32), NamedSharding(mesh, P(None, "streams")))
        t0 = time.perf_counter()
        state, raw = sharded_chunk_step(state, vals_d, ts_d, cfg, mesh)
        raw = np.asarray(jax.device_get(raw))
        walls.append(time.perf_counter() - t0)
        assert raw.shape == (T, G) and np.isfinite(raw).all()
        print(f"chunk {c}: {walls[-1]:.1f}s ({T * G / walls[-1]:.0f} metrics/s on "
              f"this CPU host)", file=sys.stderr, flush=True)

    peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024**2
    print(json.dumps({
        "streams": G, "devices": args.devices, "ticks_per_chunk": T,
        "perm_bits": args.perm_bits, "bytes_per_stream": per,
        "state_gib": round(per * G / 1024**3, 2),
        "state_build_s": round(t_init, 1),
        "chunk_walls_s": [round(w, 1) for w in walls],
        "peak_rss_gib": round(peak_rss, 1),
        "note": "virtual CPU mesh: validates sharded execution at scale, "
                "not per-chip throughput (python -m benchmark.run measures that)",
    }), flush=True)


if __name__ == "__main__":
    main()
