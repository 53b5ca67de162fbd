"""Roofline / MFU accounting for the fused step (r4 verdict item 3).

"Actually fast, or just correct?" — this script closes the loop between the
measured metrics/s numbers and what a v5e-1 can sustain. For each config it
compiles the real chunked step and reads XLA's own cost model
(`compiled.cost_analysis()`: FLOPs + bytes accessed for the optimized HLO),
then divides by the chip peaks:

    TPU v5e (1 chip): ~197 TFLOP/s bf16, ~49 TFLOP/s f32 (MXU),
                      ~819 GB/s HBM bandwidth, 16 GiB HBM.

Outputs reports/roofline.json: per config, FLOPs/tick, HBM bytes/tick,
arithmetic intensity, the bandwidth- and compute-bound time floors, the
MEASURED ms/tick (from the committed silicon profiles, provenance noted),
and the implied utilizations. The point is to NAME the binding resource:
if measured time >> max(bytes/BW, flops/peak), the kernel is neither
HBM- nor MXU-bound — it is latency/occupancy-bound (many small serialized
ops), and the next lever is fusion/batching, not arithmetic.

    python scripts/roofline.py                  # on the chip (cost model of
                                                #   the TPU-lowered HLO)
    RTAP_FORCE_CPU=1 python scripts/roofline.py # CPU-lowered HLO (flagged)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import enable_compile_cache, require_device  # noqa: E402

# v5e-1 peaks (public spec: 394 TOPS int8 / 197 TFLOPs bf16 per chip,
# 819 GB/s HBM BW, 16 GiB HBM)
PEAK_BF16_FLOPS = 197e12
PEAK_F32_FLOPS = 49e12
PEAK_HBM_BPS = 819e9

# Committed silicon measurements (ms/tick, T=32 chunked, full learning
# unless noted) — the provenance strings name the artifact logs.
MEASURED = {
    "preset_256col_G1024": (31.95, "2026-08 chip run, profile_flat step (log in git history): G=1024 "
                                   "31.95 ms/tick (32,050 metrics/s)"),
    "eighth_32col_G1024": (14.65, "2026-08 chip run, profile_eighth step (log in git history): G=1024 "
                                  "14.65 ms/tick (69,876 metrics/s)"),
    "eighth_32col_k2_G1024": (7.85, "2026-08 chip run, profile_eighth_k2 step (log in git history): "
                                    "G=1024 7.85 ms/tick (130,380 metrics/s)"),
    "eighth_32col_G65536": (1555.4, "2026-08 chip run, profile_32col_bigg step (log in git history): "
                                    "G=65536 1555.4 ms/tick (42,134 "
                                    "metrics/s) — the residency frontier"),
}


def log(msg: str) -> None:
    print(f"[roofline] {msg}", file=sys.stderr, flush=True)


def _config(name: str):
    from rtap_tpu.config import cluster_preset, scaled_cluster_preset

    if name.startswith("preset_256col"):
        cfg = cluster_preset()
    else:
        cfg = scaled_cluster_preset(32)
    if "_k2_" in name or name.endswith("_k2"):
        cfg = cfg.with_learn_every(2)
    return cfg


def cost_of(cfg, G: int, T: int) -> dict:
    """Compile chunk_step at (G, T) and pull XLA's cost analysis."""
    import jax
    import jax.numpy as jnp

    from rtap_tpu.models.state import init_state, state_nbytes
    from rtap_tpu.ops.step import chunk_step, replicate_state

    state = replicate_state(init_state(cfg, seed=0), G)
    vals = jnp.zeros((T, G, 1), jnp.float32)
    ts = jnp.zeros((T, G), jnp.int32)

    def _chunk_learn(s, v, t):
        return chunk_step(s, v, t, cfg, learn=True)

    fn = jax.jit(_chunk_learn, donate_argnums=(0,))
    compiled = fn.lower(state, vals, ts).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns [dict]
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    mem = compiled.memory_analysis()
    out = {
        "flops_per_chunk": flops,
        "bytes_accessed_per_chunk": byt,
        "flops_per_tick": flops / T,
        "bytes_per_tick": byt / T,
        "state_bytes_per_stream": int(state_nbytes(cfg)["total"]),
    }
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                out[k] = int(v)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=os.path.join(REPO, "reports",
                                                  "roofline.json"))
    ap.add_argument("--T", type=int, default=32)
    ap.add_argument("--configs", default=None,
                    help="comma-separated subset of the config names "
                         "(cheap CPU drives skip the G=65536 compile)")
    args = ap.parse_args()

    require_device()  # no TPU and no explicit CPU choice -> fail here
    import jax

    enable_compile_cache()
    platform = jax.devices()[0].platform

    configs = {
        "preset_256col_G1024": ("preset_256col", 1024),
        "eighth_32col_G1024": ("eighth_32col", 1024),
        "eighth_32col_k2_G1024": ("eighth_32col_k2", 1024),
        "eighth_32col_G65536": ("eighth_32col", 65536),
    }
    if args.configs:
        picked = args.configs.split(",")
        bad = set(picked) - set(configs)
        if bad:
            raise SystemExit(f"unknown configs {sorted(bad)}")
        configs = {k: v for k, v in configs.items() if k in picked}
    rows = {}
    for name, (cfg_name, G) in configs.items():
        t0 = time.time()
        try:
            c = cost_of(_config(cfg_name), G, args.T)
        except Exception as e:  # noqa: BLE001 — a too-big compile must not
            # kill the smaller configs' accounting
            log(f"{name}: FAILED {type(e).__name__}: {str(e)[:200]}")
            rows[name] = {"error": str(e)[:300]}
            continue
        log(f"{name}: compiled in {time.time() - t0:.0f}s")
        bw_floor_ms = c["bytes_per_tick"] / PEAK_HBM_BPS * 1e3
        # the kernels are predominantly f32 elementwise/compare with f32
        # one-hot matmuls — credit the F32 peak (bf16 would flatter us 4x)
        fl_floor_ms = c["flops_per_tick"] / PEAK_F32_FLOPS * 1e3
        row = {
            **c,
            "arithmetic_intensity_flops_per_byte": round(
                c["flops_per_tick"] / max(c["bytes_per_tick"], 1), 3),
            "hbm_floor_ms_per_tick": round(bw_floor_ms, 3),
            "f32_mxu_floor_ms_per_tick": round(fl_floor_ms, 4),
        }
        meas = MEASURED.get(name)
        if meas and platform == "tpu":
            ms, prov = meas
            row.update({
                "measured_ms_per_tick": ms,
                "measured_provenance": prov,
                "hbm_utilization_pct": round(100 * bw_floor_ms / ms, 2),
                "f32_mxu_utilization_pct": round(100 * fl_floor_ms / ms, 3),
                "latency_bound_factor": round(
                    ms / max(bw_floor_ms, fl_floor_ms), 1),
            })
        rows[name] = row

    out = {
        "platform": platform,
        "chip_peaks": {"bf16_flops": PEAK_BF16_FLOPS,
                       "f32_flops": PEAK_F32_FLOPS,
                       "hbm_bytes_per_s": PEAK_HBM_BPS,
                       "hbm_bytes": 16 * (1 << 30)},
        "T": args.T,
        "note": ("cost model = XLA cost_analysis of the optimized HLO on "
                 "this platform; measured times are the committed T=32 "
                 "chunked silicon profiles (full learning). Utilization = "
                 "resource floor / measured. A latency_bound_factor >> 1 "
                 "means the step is bound by op-dispatch/serialization, "
                 "not by HBM or MXU."),
        "configs": rows,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({k: {kk: v[kk] for kk in
                          ("hbm_utilization_pct", "latency_bound_factor")
                          if kk in v}
                      for k, v in rows.items() if "error" not in v}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
