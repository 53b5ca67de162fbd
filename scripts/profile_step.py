"""Profile the fused stream-group step on the real chip.

Breaks the per-tick cost down by (a) group size scaling, (b) component
ablation (encode / SP / TM, learn on/off), and (c) — with --report — a
programmatic per-region cost extraction of the compiled program (entry-
computation region counts by opcode, XLA cost/memory analysis), so
optimization effort lands on the measured bottleneck (VERDICT r1
next-step 1) and the "where does the 10x latency-bound gap go" question
(reports/roofline.json) gets a committed, machine-readable answer. Send
it to the chip through the chip tool (one process per chip):

    python scripts/profile_step.py [--trace DIR] [--report reports/profile_r06.json]

Prints a table to stderr; with --trace, wraps one measured chunk in a
jax.profiler trace for xprof; with --report, writes the full breakdown +
region analysis as one JSON artifact (platform-labeled — a CPU-drive run
is marked as such, never passed off as silicon).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from rtap_tpu.utils.platform import require_device  # noqa: E402

require_device()  # no TPU and no explicit CPU choice -> fail here

import jax  # noqa: E402
import jax.numpy as jnp
import numpy as np

from rtap_tpu.config import ModelConfig, cluster_preset
from rtap_tpu.models.state import init_state
from rtap_tpu.ops.encoders_tpu import bind_offsets, encode_device
from rtap_tpu.ops.sp_tpu import sp_step
from rtap_tpu.ops.tm_tpu import tm_step
from rtap_tpu.ops.step import chunk_step, replicate_state_device


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def make_inputs(G, T, n_fields, seed=0):
    rng = np.random.Generator(np.random.Philox(key=(seed, 77)))
    vals = (35 + 20 * rng.random((T, G, n_fields))).astype(np.float32)
    ts = (1_700_000_000 + np.arange(T)[:, None] + np.zeros((1, G), np.int64)).astype(np.int32)
    return vals, ts


def time_fn(fn, state, iters=3, warmup=1):
    """fn(state) -> (state, aux); state buffers are donated, so thread them."""
    for _ in range(warmup):
        state, _ = fn(state)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, _ = fn(state)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / iters


def region_analysis(cfg, G: int, T: int) -> dict:
    """Programmatic per-region cost extraction of the compiled fused step.

    Compiles the REAL chunk_step at (G, T) and reads, from the optimized
    HLO itself (no trace viewer in the loop): the entry-computation
    instruction count — each top-level instruction is one scheduled region
    / kernel launch, the currency the roofline's latency_bound_factor says
    we overspend — a histogram by opcode, the fusion-region count, and
    XLA's cost/memory analysis. Platform-dependent by construction: the
    committed artifact labels the platform, and the silicon number is the
    one that decides (hw_session step profile_r06)."""
    import re

    from rtap_tpu.models.state import init_state
    from rtap_tpu.ops.step import chunk_step

    state = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.asarray(x)[None], (G, *np.shape(x))),
        init_state(cfg, seed=0))
    vals = jnp.zeros((T, G, cfg.n_fields), jnp.float32)
    ts = jnp.zeros((T, G), jnp.int32)
    def _chunk_learn(s, v, t):
        return chunk_step(s, v, t, cfg, learn=True)

    fn = jax.jit(_chunk_learn)
    compiled = fn.lower(state, vals, ts).compile()

    txt = compiled.as_text()

    def op_histogram(block: str) -> dict[str, int]:
        # one instruction per line: `%name = <shape> opcode(...)`; the
        # shape may be a spaced tuple, so the opcode is the FIRST
        # word-followed-by-( after the `=`
        ops: dict[str, int] = {}
        for line in block.splitlines():
            m = re.search(r"=\s+.*?\s([a-z][a-z0-9_-]*)\(", line)
            if m:
                ops[m.group(1)] = ops.get(m.group(1), 0) + 1
        return ops

    # entry computation: from "ENTRY %name" to its closing brace
    entry = txt[txt.index("ENTRY "):] if "ENTRY " in txt else txt
    entry = entry[:entry.index("\n}") + 2] if "\n}" in entry else entry
    ops = op_histogram(entry)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    mem = compiled.memory_analysis()
    out = {
        "entry_instructions": sum(ops.values()),
        "fusion_regions": ops.get("fusion", 0),
        "while_loops": ops.get("while", 0),
        "opcode_histogram": dict(sorted(ops.items(), key=lambda kv: -kv[1])),
        "flops_per_chunk": float(ca.get("flops", 0.0)),
        "bytes_accessed_per_chunk": float(ca.get("bytes accessed", 0.0)),
    }
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                out[k] = int(v)
    # the scan body is where per-tick dispatch gaps live: resolve the
    # while instruction's body= computation and count ITS regions — each
    # is a per-tick dispatch boundary, paid T times per chunk
    wm = re.search(r"\swhile\(.*?body=%?([\w.\-]+)", entry)
    if wm:
        bm = re.search(r"\n%" + re.escape(wm.group(1)) + r"\s.*?\n}",
                       txt, re.S)
        if bm:
            bops = op_histogram(bm.group(0))
            out["scan_body_instructions"] = sum(bops.values())
            out["scan_body_fusions"] = bops.get("fusion", 0)
            out["scan_body_opcode_histogram"] = dict(
                sorted(bops.items(), key=lambda kv: -kv[1]))
    return out


# ---- ablation kernels: scan-over-T, vmap-over-G, one component only ----

def _scan_vmap(body, state, xs):
    def step(s, inp):
        return jax.vmap(body)(s, *inp)
    return jax.lax.scan(step, state, xs)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def encode_only(state, vals, ts, cfg: ModelConfig):
    def body(s, v, t):
        off, bound = bind_offsets(v, s["enc_offset"], s["enc_bound"])
        s = {**s, "enc_offset": off, "enc_bound": bound}
        sdr = encode_device(cfg, v, t, off, s["enc_resolution"])
        return s, sdr.sum()
    return _scan_vmap(body, state, (vals, ts))


@partial(jax.jit, static_argnames=("cfg", "learn"), donate_argnums=(0,))
def sp_only(state, vals, ts, cfg: ModelConfig, learn=True):
    def body(s, v, t):
        sdr = encode_device(cfg, v, t, s["enc_offset"], s["enc_resolution"])
        s, active = sp_step(s, sdr, cfg.sp, learn)
        return s, active.sum()
    return _scan_vmap(body, state, (vals, ts))


@partial(jax.jit, static_argnames=("cfg", "learn"), donate_argnums=(0,))
def tm_only(state, actives, cfg: ModelConfig, learn=True):
    from rtap_tpu.ops.tm_tpu import from_kernel_layout, to_kernel_layout

    def body(s, a):
        s, raw = tm_step(s, a, cfg.tm, learn)
        return s, raw
    def step(s, a):
        return jax.vmap(body)(s, a)
    state, out = jax.lax.scan(step, to_kernel_layout(state, cfg.tm), actives)
    return from_kernel_layout(state, cfg.tm), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None)
    ap.add_argument("--T", type=int, default=32)
    ap.add_argument("--gs", type=int, nargs="*", default=[512, 2048, 4096, 8192])
    ap.add_argument("--report", default=None,
                    help="write the full profile (G sweep, ablations, "
                         "per-region cost extraction of the compiled "
                         "program) to this JSON path")
    ap.add_argument("--region-g", type=int, default=1024,
                    help="group size the --report region extraction "
                         "compiles at (compile-only — G=1024 is the "
                         "roofline's reference point and stays cheap even "
                         "where executing it would not be)")
    ap.add_argument("--scatter", choices=("matmul", "indexed", "pallas"),
                    default=None,
                    help="TM workspace-movement strategy (ops/tm_tpu.py "
                         "SCATTER_MODE): 'indexed' moves only touched rows, "
                         "'matmul' is the one-hot MXU formulation, 'pallas' "
                         "is the VMEM TM-learning megakernel "
                         "(ops/pallas_tm.py) — A/B on hardware")
    ap.add_argument("--layout", choices=("aos", "flat"), default=None,
                    help="TM kernel tensor layout (ops/tm_tpu.py LAYOUT_MODE):"
                         " 'flat' carries [C, K*S*M] pools through the scan "
                         "(no trailing-dim tile padding), 'aos' is the 4-D "
                         "original — A/B on hardware")
    ap.add_argument("--perm-bits", type=int, default=16, choices=(0, 8, 16),
                    help="permanence storage domain of the profiled cluster "
                         "preset: u16/u8 halve HBM per stream but add per-tick "
                         "storage<->compute conversions; f32 (0) skips them — "
                         "the faster choice may differ from the denser one")
    ap.add_argument("--sweep", choices=("dense", "compact"), default=None,
                    help="TM punish/death strategy (ops/tm_tpu.py SWEEP_MODE):"
                         " 'compact' touches only the <= punish_cap+learn_cap "
                         "affected segment rows, 'dense' sweeps the full "
                         "pools — A/B on hardware")
    ap.add_argument("--dendrite", choices=("scan", "forward"), default=None,
                    help="TM dendrite-activity strategy: 'forward' gathers "
                         "the active cells' forward-index rows (ops/"
                         "fwd_index.py; state grows by the index), 'scan' "
                         "sweeps the pools — A/B on hardware")
    ap.add_argument("--fwd-impl", choices=("scatter", "matmul"), default=None,
                    help="forward-index histogram accumulation: native "
                         "scatter-add vs factored one-hot MXU contraction")
    ap.add_argument("--learn-every", type=int, default=1,
                    help="learning cadence (ModelConfig.learn_every) with "
                         "learn_full_until=0: measures the cadenced steady "
                         "state (the lax.cond schedule in ops/step.py)")
    ap.add_argument("--columns", type=int, default=None,
                    help="rescale the preset to this SP width at equal "
                         "sparsity (config.scaled_cluster_preset; the "
                         "half-size 128-col model measured BETTER f1 than "
                         "the preset at half the state — "
                         "reports/model_size_quality.json)")
    ap.add_argument("--fanout-cap", type=int, default=None,
                    help="forward-index row width F (default: 384 under "
                         "--dendrite forward — the measured diurnal-workload "
                         "fanout tail; preset default otherwise). An "
                         "undersized F trips fwd_of and corrupts the "
                         "dendrite dynamics, invalidating the A/B")
    args = ap.parse_args()

    from rtap_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    if args.scatter:
        from rtap_tpu.ops.tm_tpu import set_scatter_mode

        set_scatter_mode(args.scatter)
        log(f"TM workspace movement: {args.scatter}")
    if args.layout:
        from rtap_tpu.ops.tm_tpu import set_layout_mode

        set_layout_mode(args.layout)
        log(f"TM kernel layout: {args.layout}")
    if args.sweep:
        from rtap_tpu.ops.tm_tpu import set_sweep_mode

        set_sweep_mode(args.sweep)
        log(f"TM punish/death sweep: {args.sweep}")
    if args.dendrite:
        from rtap_tpu.ops.tm_tpu import set_dendrite_mode

        set_dendrite_mode(args.dendrite)
        log(f"TM dendrite strategy: {args.dendrite}")
    if args.fwd_impl:
        from rtap_tpu.ops.tm_tpu import set_fwd_impl

        set_fwd_impl(args.fwd_impl)
        log(f"forward-index histogram impl: {args.fwd_impl}")

    if args.columns:
        from rtap_tpu.config import scaled_cluster_preset

        cfg = scaled_cluster_preset(args.columns, perm_bits=args.perm_bits)
        log(f"scaled preset: {args.columns} columns")
    else:
        cfg = cluster_preset(perm_bits=args.perm_bits)
    if args.fanout_cap or args.dendrite == "forward":
        import dataclasses

        F = args.fanout_cap or 384
        cfg = dataclasses.replace(cfg, tm=dataclasses.replace(cfg.tm, fanout_cap=F))
        log(f"forward-index fanout cap: {F}")
    if args.learn_every > 1:
        import dataclasses

        # learn_full_until=0: cadence applies from tick 0 so the measured
        # steady state is the cadenced one (quality study owns the maturity
        # window; this is a pure throughput probe)
        cfg = dataclasses.replace(cfg, learn_every=args.learn_every)
        log(f"learning cadence: every {args.learn_every} ticks")
    T = args.T
    log(f"platform: {jax.devices()[0].platform} {jax.devices()[0].device_kind} "
        f"(perm_bits={args.perm_bits})")

    report = {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "T": T,
        "perm_bits": args.perm_bits,
        "columns": args.columns,
        "learn_every": args.learn_every,
        "modes": None,  # filled below (import deferred until flags applied)
        "g_sweep": {},
        "ablations_ms_per_tick": {},
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    from rtap_tpu.ops.tm_tpu import (
        dendrite_mode, layout_mode, scatter_mode, sweep_mode,
    )

    report["modes"] = (f"{layout_mode(cfg.tm)}/{scatter_mode(cfg.tm)}/{sweep_mode()}"
                       f"/{dendrite_mode()}")

    log("\n== G scaling, full step (learn=True) ==")
    results = {}
    for G in args.gs:
        try:
            state = replicate_state_device(init_state(cfg, 0), G)
            vals, ts = make_inputs(G, T, cfg.n_fields)
            dt = time_fn(lambda s: chunk_step(s, vals, ts, cfg, True), state, iters=2)
            per_tick = dt / T
            rate = G * T / dt
            results[G] = rate
            report["g_sweep"][str(G)] = {
                "ms_per_tick": round(per_tick * 1e3, 3),
                "metrics_per_s": round(rate, 1),
            }
            log(f"G={G:6d}: {per_tick*1e3:8.2f} ms/tick  {rate:10.0f} metrics/s")
        except Exception as e:
            report["g_sweep"][str(G)] = {
                "failed": f"{type(e).__name__}: {str(e)[:160]}"}
            log(f"G={G:6d}: FAILED {type(e).__name__}: {str(e)[:120]}")

    if not results:
        # every probed G failed (OOM-frontier probes do this by design):
        # the FAILED lines above ARE the result — exit 0 so a watcher
        # step wrapping this run doesn't burn retries on a deterministic
        # outcome
        log("\nno G succeeded; skipping ablations")
        return 0
    # ablations at the LARGEST G that also leaves room for their extra
    # buffers: at the OOM frontier the main sweep fits but the ablation
    # temporaries (fresh state replicas, TM-only activation masks) do not
    # — each row is guarded so a frontier run still reports what fits,
    # and a row failure can never fail the step (watcher-attempt safety)
    G = max(g for g in results)
    log(f"\n== ablations at G={G}, T={T} ==")
    vals, ts = make_inputs(G, T, cfg.n_fields)

    report["ablation_G"] = G

    def ablate(label, fn):
        try:
            st = replicate_state_device(init_state(cfg, 0), G)
            dt = time_fn(fn, st, iters=2)
            report["ablations_ms_per_tick"][label.strip()] = round(dt / T * 1e3, 3)
            log(f"{label}: {dt/T*1e3:8.2f} ms/tick")
        except Exception as e:
            report["ablations_ms_per_tick"][label.strip()] = (
                f"FAILED {type(e).__name__}")
            log(f"{label}: FAILED {type(e).__name__}: {str(e)[:100]}")

    ablate("full learn=True ", lambda s: chunk_step(s, vals, ts, cfg, True))
    ablate("full learn=False", lambda s: chunk_step(s, vals, ts, cfg, False))
    ablate("encode only     ", lambda s: encode_only(s, vals, ts, cfg))
    ablate("enc+SP learn    ", lambda s: sp_only(s, vals, ts, cfg, True))
    ablate("enc+SP infer    ", lambda s: sp_only(s, vals, ts, cfg, False))

    # TM alone: feed plausible active-column masks (k of C)
    try:
        rng = np.random.Generator(np.random.Philox(key=(1, 78)))
        C, k = cfg.sp.columns, cfg.sp.num_active_columns
        acts = np.zeros((T, G, C), bool)
        idx = rng.integers(0, C, (T, G, k))
        np.put_along_axis(acts, idx, True, axis=-1)
        acts_d = jnp.asarray(acts)
        ablate("TM only learn   ", lambda s: tm_only(s, acts_d, cfg, True))
        ablate("TM only infer   ", lambda s: tm_only(s, acts_d, cfg, False))
    except Exception as e:
        log(f"TM only         : FAILED {type(e).__name__}: {str(e)[:100]}")

    if args.trace:
        st = replicate_state_device(init_state(cfg, 0), G)
        chunk_step(st, vals, ts, cfg, True)  # compiled above; warm anyway
        st = replicate_state_device(init_state(cfg, 0), G)
        with jax.profiler.trace(args.trace):
            st, raw = chunk_step(st, vals, ts, cfg, True)
            jax.block_until_ready(raw)
        log(f"trace written to {args.trace}")
        report["trace_dir"] = args.trace

    if args.report:
        # per-region cost extraction of the program the sweep measured:
        # region counts name where the latency-bound factor goes (dispatch
        # edges between regions), cost/memory analysis ties them to the
        # roofline floors
        try:
            log("\n== per-region cost extraction (compiled HLO) ==")
            ra = region_analysis(cfg, args.region_g, T)
            ra["G"] = args.region_g
            report["region_analysis"] = ra
            log(f"entry instructions: {ra['entry_instructions']} "
                f"(fusions {ra['fusion_regions']}); scan body: "
                f"{ra.get('scan_body_instructions', '?')} instructions / "
                f"{ra.get('scan_body_fusions', '?')} fusions")
        except Exception as e:  # keep the measured numbers even if HLO
            # introspection breaks on some backend
            report["region_analysis"] = {"failed": f"{type(e).__name__}: {e}"}
            log(f"region analysis FAILED: {type(e).__name__}: {str(e)[:120]}")
        os.makedirs(os.path.dirname(os.path.abspath(args.report)), exist_ok=True)
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
        log(f"report written to {args.report}")


if __name__ == "__main__":
    main()
