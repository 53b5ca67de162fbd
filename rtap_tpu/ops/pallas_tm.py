"""Pallas TPU megakernel: the WHOLE TM learning pass fused in VMEM.

Round-4 measured the dendrite-only Pallas kernel LOSING to XLA (-13%,
SCALING.md silicon A/B): hand-scheduling ONE already-cheap pass just added
dispatch edges around it. The round-6 profile (reports/profile_r06.json,
scripts/profile_step.py --report) places ~99% of a learn tick inside the TM
learning pass while the chip is ~90% idle (roofline latency_bound_factor
10.0) — the cost is op-dispatch/serialization BETWEEN the pass's XLA
regions, not arithmetic. This kernel therefore fuses the granularity the
round-4 attempt got wrong: dendrite activity + workspace movement +
reinforce/grow — the entire per-tick pool traversal — as ONE kernel whose
intermediates never leave VMEM:

    alloc     clear the burst-new segment's synapse slots
    reinforce +inc toward prev-active presynaptic cells, -dec elsewhere,
              on the learning segments
    grow      add winner-cell synapses ascending, evicting the weakest
              occupied slots when free slots run short
    punish    -pdec on matching segments in non-active columns
    death     presyn := -1 at permanence <= 0; per-segment synapse counts
    dendrite  packed-column activity + connected/potential counts for t+1

Key design choice vs the XLA formulation (ops/tm_tpu.py): NO column-compact
workspace. The gather→learn→scatter movement exists to avoid full-pool HBM
round trips; with the pool VMEM-resident the dense traversal is free of
exactly that cost, so the kernel runs per-segment lanes [n_seg, M] directly
and the learning decisions arrive as a per-segment metadata array. All
DECISION logic (column categorization, allocation targets, capacity
truncation) stays in XLA on the [C, K, S]-scale tensors — it is 32 KB-scale
work; the kernel owns the MB-scale pool traversal.

Semantics are bit-identical to the default XLA path (RTAP_TM_SCATTER=matmul
with dense sweeps): the workspace truncation (first col_cap active columns,
first learn_cap learning segments in ascending (c, k, s) order) is
reproduced exactly by `tm_learn_pallas`'s mask prep, and every arithmetic
expression mirrors tm_tpu.py's f32 forms (integer-valued in quantized
domains, exact below 2^24). Asserted by tests/parity/test_pallas_tm.py via
interpreter mode on CPU, across the perm domains and under vmap, and on the
chip by chip_smoke.py's score phase (scaled_cluster_preset(32) against the
default path, bit for bit).

Strategy wiring: RTAP_TM_SCATTER=pallas (ops/tm_tpu.py mode table). OFF by
default — shipping an unmeasured kernel as the default would repeat the
round-1 mistake; it becomes the default or is deleted on a chip A/B
(ROADMAP queue 1 item 1). Incompatible with RTAP_TM_DENDRITE=forward
(the kernel computes dendrite counts itself) and RTAP_TM_SWEEP=compact
(it fuses the DENSE punish/death semantics); tm_step rejects both combos
loudly. Inference ticks (learn=False) keep the XLA dendrite path — the
learning pass is ~99% of the tick, the dendrite pass is already cheap.

Known v1 limits (compiled for v5e, ISSUE 21 — docs/KERNELS.md has the
numbers): the [n_seg, M] layout leaves M (<= 32) lanes per row, which the
TPU tiler pads to 128, and the unrolled winner loops keep ~2 such arrays
live per iteration — so the compiler's scoped-VMEM charge is ~n_seg x 512 B
x (18 + 2W). It compiles at scaled_cluster_preset(32) and (64) under the
raised limit below; scaled_cluster_preset(128) (108 MiB) and the cluster
preset itself (~370 MiB against a 128 MiB core) are refused by the guard
before the compiler is asked. Whether v2 re-blocks lanes to [M, n_seg]
waits for the chip A/B (ROADMAP queue 1 item 1). The winner-loop unrolls
W = col_cap * cells_per_column times and is guarded off at NAB scale (1280).

Interpreter mode is for small parity tests only — orders of magnitude
slower than XLA — and is something a test asks for by argument
(``set_scatter_mode("pallas", interpret=True)``): off a TPU the
non-interpreted kernel raises at trace time instead of silently
interpreting, and the guards refuse large shapes instead of hanging.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM limit handed to Mosaic (its default is 16 MiB; a v5e core has
# 128 MiB). One stream's pools plus the kernel's temporaries must fit it —
# under vmap the batch becomes a grid axis, so one stream is resident at a
# time. What the v5e compiler actually charges, fitted to its own refusals
# in described-topology compiles at M=12 (ISSUE 21; docs/KERNELS.md has the
# table): every [n_seg, <=128] value is lane-padded to 128 lanes (512 B per
# row), ~18 of them are live at once plus ~2 per unrolled winner iteration,
# and up to ~14 MiB more that follows the batch size and the limit itself.
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_VMEM_SLACK_BYTES = 16 * 1024 * 1024
# Interpreter mode (off-TPU) is for parity tests only; refuse big shapes
# instead of silently hanging for minutes.
_INTERPRET_MAX_SYNAPSES = 1 << 18
# The grow pass unrolls over the winner list twice; beyond this the trace
# (and the Mosaic schedule) blows up — the NAB preset (W=1280) is refused.
_MAX_WINNER_UNROLL = 512

_META_COLS = 5  # learn, alloc, grow, punish, n_grow


def _mega_kernel(K, M, N, W, Ac, consts,
                 presyn_ref, perm_ref, meta_ref,
                 pids_ref, pmasks_ref, wids_ref, aids_ref, amasks_ref,
                 presyn_out, perm_out, nsyn_out, conn_out, pot_out):
    """One stream's full TM learning pass on [n_seg, M] pools (see module
    docstring for the stage list). `consts` are the permanence-domain
    constants (trace-time floats); `pdec` None skips the punish stage."""
    p_inc, p_dec, p_init, p_one, p_zero, p_thr, pdec = consts
    presyn = presyn_ref[:]  # [n_seg, M] i32
    perm = perm_ref[:]  # [n_seg, M] f32 (domain values)
    meta = meta_ref[:]  # [n_seg, 5] i32
    learn = meta[:, 0:1] > 0
    alloc = meta[:, 1:2] > 0
    grow = meta[:, 2:3] > 0
    punish = meta[:, 3:4] > 0
    n_grow = meta[:, 4:5]

    # --- burst-new allocation: clear the allocated segment's slots ---
    presyn = jnp.where(alloc, -1, presyn)
    perm = jnp.where(alloc, 0.0, perm)

    def packed_act(pres, ids_ref, masks_ref):
        # packed-column membership (tm_tpu._presyn_active_packed, unrolled
        # over the tiny Ac like the r4 dendrite kernel)
        c_pre = pres // K  # -1 -> -1 (floor): never equals a valid col id
        k_pre = pres % K  # -1 -> K-1, masked by pres >= 0 below
        msk = jnp.zeros_like(pres)
        for i in range(Ac):
            msk = msk + jnp.where(c_pre == ids_ref[0, i], masks_ref[0, i], 0)
        return (pres >= 0) & (((msk >> k_pre) & 1) > 0)

    # --- reinforce learning segments toward prev-active cells ---
    act = packed_act(presyn, pids_ref, pmasks_ref)
    exists = presyn >= 0
    perm = jnp.where(
        learn,
        jnp.clip(perm + p_inc * act - p_dec * (exists & ~act), 0.0, p_one),
        perm,
    )

    # --- grow pass 1: eligible-winner count per segment (eligibility reads
    # the PRE-eviction pool, exactly like _grow_compact's membership) ---
    presyn_pre = presyn
    n_seg = presyn.shape[0]
    n_elig = jnp.zeros((n_seg, 1), jnp.int32)
    for w in range(W):
        wid = wids_ref[0, w]
        already = jnp.sum(
            (presyn_pre == wid).astype(jnp.int32), axis=1, keepdims=True) > 0
        n_elig = n_elig + ((wid < N) & ~already).astype(jnp.int32)
    n_new = jnp.minimum(n_elig, jnp.maximum(n_grow, 0))
    n_new = jnp.where(grow, n_new, 0)  # non-growing segments add nothing

    # --- evict weakest occupied synapses when free slots run short:
    # stable ascending rank by (permanence, slot), compare-count form ---
    occupied = presyn >= 0
    n_free = M - jnp.sum(occupied.astype(jnp.int32), axis=1, keepdims=True)
    short = n_new - n_free
    key = jnp.where(occupied, perm, jnp.float32(jnp.inf))
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)
    ranks = jnp.zeros((n_seg, M), jnp.int32)
    for mp in range(M):
        kmp = key[:, mp:mp + 1]
        ranks = ranks + ((kmp < key) | ((kmp == key) & (mp < slot))).astype(jnp.int32)
    evict = occupied & (ranks < short)
    presyn = jnp.where(evict, -1, presyn)
    perm = jnp.where(evict, 0.0, perm)

    # --- fill free slots ascending with chosen winners ascending ---
    free = presyn < 0
    frank_cols = []  # 0-based rank of each slot among free slots
    accf = jnp.zeros((n_seg, 1), jnp.int32)
    for m in range(M):
        frank_cols.append(accf)
        accf = accf + free[:, m:m + 1].astype(jnp.int32)
    frank = jnp.concatenate(frank_cols, axis=1)
    fill = jnp.zeros((n_seg, M), jnp.int32)
    accr = jnp.zeros((n_seg, 1), jnp.int32)
    for w in range(W):
        wid = wids_ref[0, w]
        already = jnp.sum(
            (presyn_pre == wid).astype(jnp.int32), axis=1, keepdims=True) > 0
        elig = (wid < N) & ~already
        rank_w = accr + elig.astype(jnp.int32)  # 1-based among eligible
        accr = rank_w
        chosen = elig & (rank_w <= n_grow)
        fill = jnp.where(chosen & (frank == rank_w - 1), wid, fill)
    assign = free & (frank < n_new) & grow
    presyn = jnp.where(assign, fill, presyn)
    perm = jnp.where(assign, p_init, perm)

    # --- punish matching segments in non-active columns (dense sweep
    # semantics; punished columns are disjoint from learning columns, so
    # the pre-grow membership `act` is still exact there) ---
    if pdec is not None:
        perm = jnp.where(punish & act, jnp.maximum(perm - pdec, p_zero), perm)

    # --- synapse death at permanence <= 0, per-segment occupancy ---
    dead = (presyn >= 0) & (perm <= p_zero)
    presyn = jnp.where(dead, -1, presyn)
    nsyn = jnp.sum((presyn >= 0).astype(jnp.int32), axis=1, keepdims=True)

    # --- dendrite activity for t+1 on the updated pools ---
    dact = packed_act(presyn, aids_ref, amasks_ref)
    pot = jnp.sum(dact.astype(jnp.int32), axis=1, keepdims=True)
    conn = jnp.sum((dact & (perm >= p_thr)).astype(jnp.int32),
                   axis=1, keepdims=True)

    presyn_out[:] = presyn
    perm_out[:] = perm
    nsyn_out[:] = nsyn
    conn_out[:] = conn
    pot_out[:] = pot


def _guard_shapes(C, K, S, M, W, interpret):
    n_syn = C * K * S * M
    if interpret and n_syn > _INTERPRET_MAX_SYNAPSES:
        raise ValueError(
            f"Pallas TM megakernel in INTERPRETER mode with {n_syn} synapses "
            f"(> {_INTERPRET_MAX_SYNAPSES}): this path exists for small "
            "parity tests; on CPU leave RTAP_TM_SCATTER at the default "
            "(the XLA formulation is the fast path there)"
        )
    if W > _MAX_WINNER_UNROLL:
        raise ValueError(
            f"Pallas TM megakernel with winner-list length {W} (> "
            f"{_MAX_WINNER_UNROLL}): the grow pass unrolls over it twice — "
            "this preset (col_cap * cells_per_column too large, e.g. the NAB "
            "preset) needs the XLA path"
        )
    if interpret:
        return  # the interpreter has no VMEM; its size guard is above
    charged = C * K * S * max(M, 128) * 4 * (18 + 2 * W) + _VMEM_SLACK_BYTES
    if charged > _VMEM_LIMIT_BYTES:
        raise ValueError(
            f"Pallas TM megakernel: the TPU compiler would charge "
            f"~{charged >> 20} MiB of scoped VMEM for one stream at "
            f"[C={C}, K={K}, S={S}, M={M}], winner list {W} (rows are "
            f"lane-padded to 128; limit {_VMEM_LIMIT_BYTES >> 20} MiB of "
            "the core's 128) — this preset is too large for the unblocked "
            "v1 kernel; keep RTAP_TM_SCATTER=matmul for it"
        )


# rtap: twin[TMOracle] — megakernel twin of the default TM learning path;
# bit-parity in interpreter mode: tests/parity/test_pallas_tm.py
def tm_learn_pallas(
    cfg,
    dom,
    presyn: jnp.ndarray,  # kernel-layout pool (any int dtype; -1 = empty)
    syn_perm: jnp.ndarray,  # kernel-layout pool (storage domain)
    seg_last: jnp.ndarray,  # kernel-layout [C, K*S] or [C, K, S] i32
    seg_pot4: jnp.ndarray,  # i32 [C, K, S] (prev step)
    matching_seg4: jnp.ndarray,  # bool [C, K, S] (prev step)
    learn_mask: jnp.ndarray,  # bool [C, K, S] (predicted + burst-match)
    alloc,  # (alloc_col [C], bn_k [C], bn_s [C]) from _segment_learning_mask
    active_cols: jnp.ndarray,  # bool [C]
    have_winners: jnp.ndarray,  # bool scalar
    it: jnp.ndarray,  # i32 scalar (this step's iteration stamp)
    pcol_ids: jnp.ndarray,  # [Ac] packed prev-active columns
    pcol_masks: jnp.ndarray,
    p_cols: jnp.ndarray,  # i32 scalar: TOTAL prev-active columns (overflow)
    winner_ids: jnp.ndarray,  # [Ac*K] prev winner cell ids (fills = N)
    acol_ids: jnp.ndarray,  # [Ac] packed CURRENT active cells (dendrite)
    acol_masks: jnp.ndarray,
    interpret: bool = False,
):
    """XLA-side harness for the megakernel: reproduce the workspace
    truncation as dense masks, call the kernel, apply the [C, K, S]-scale
    epilogue (seg_last stamping/death). Returns
    (presyn' i32 [n_seg, M], perm' f32 [n_seg, M], seg_last' i32 [n_seg],
    conn [n_seg], pot [n_seg], overflow bool scalar) — caller casts/reshapes
    back to the pool layout/domain.
    """
    C = active_cols.shape[0]
    K = cfg.cells_per_column
    S = cfg.max_segments_per_cell
    M = cfg.max_synapses_per_segment
    n_seg = C * K * S
    N = C * K
    L, Ac = cfg.learn_cap, cfg.col_cap
    W = winner_ids.shape[0]
    G = cfg.new_synapse_count
    if not interpret:
        from rtap_tpu.ops.tm_tpu import _tpu_paths

        if not _tpu_paths():
            raise ValueError(
                "RTAP_TM_SCATTER=pallas compiles for a TPU only and this "
                f"backend is {jax.default_backend()!r}; interpreter mode is "
                "for parity tests, which ask for it by argument "
                "(set_scatter_mode('pallas', interpret=True))"
            )
    _guard_shapes(C, K, S, M, W, interpret)

    # --- the workspace truncation, as dense masks: the XLA path captures
    # the first Ac active columns ascending, then the first L learning
    # segments in ascending (c, k, s) order — identical selection here ---
    alloc_col, bn_k, bn_s = alloc
    burst_new = alloc_col < C
    captured = active_cols & (jnp.cumsum(active_cols.astype(jnp.int32)) <= Ac)
    kk = jnp.arange(K, dtype=jnp.int32)
    ss = jnp.arange(S, dtype=jnp.int32)
    alloc_seg = (
        (burst_new & captured)[:, None, None]
        & (kk[None, :, None] == bn_k[:, None, None])
        & (ss[None, None, :] == bn_s[:, None, None])
    )  # [C, K, S]
    ws_learn = ((learn_mask & captured[:, None, None]) | alloc_seg).reshape(-1)
    learn_trunc = ws_learn & (jnp.cumsum(ws_learn.astype(jnp.int32)) <= L)
    grow_seg = learn_trunc & have_winners
    n_grow = (G - jnp.where(alloc_seg, 0, seg_pot4).reshape(-1)).astype(jnp.int32)

    pdec = None
    if cfg.predicted_segment_decrement > 0.0:
        pdec = float(dom.rate(cfg.predicted_segment_decrement))
        punish_seg = (matching_seg4 & ~active_cols[:, None, None]).reshape(-1)
    else:
        punish_seg = jnp.zeros(n_seg, bool)

    meta = jnp.stack(
        [
            learn_trunc.astype(jnp.int32),
            alloc_seg.reshape(-1).astype(jnp.int32),
            grow_seg.astype(jnp.int32),
            punish_seg.astype(jnp.int32),
            n_grow,
        ],
        axis=1,
    )  # [n_seg, _META_COLS]

    consts = (
        float(dom.rate(cfg.permanence_increment)),
        float(dom.rate(cfg.permanence_decrement)),
        float(dom.rate(cfg.initial_permanence)),
        float(dom.one),
        float(dom.zero),
        float(dom.threshold(cfg.connected_permanence)),
        pdec,
    )
    kernel = functools.partial(_mega_kernel, K, M, N, W, Ac, consts)
    i32, f32 = jnp.int32, jnp.float32
    presyn_n, perm_n, nsyn, conn, pot = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((n_seg, M), i32),
            jax.ShapeDtypeStruct((n_seg, M), f32),
            jax.ShapeDtypeStruct((n_seg, 1), i32),
            jax.ShapeDtypeStruct((n_seg, 1), i32),
            jax.ShapeDtypeStruct((n_seg, 1), i32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(
        presyn.reshape(n_seg, M).astype(i32),
        syn_perm.reshape(n_seg, M).astype(f32),
        meta,
        pcol_ids.reshape(1, Ac).astype(i32),
        pcol_masks.reshape(1, Ac).astype(i32),
        winner_ids.reshape(1, W).astype(i32),
        acol_ids.reshape(1, Ac).astype(i32),
        acol_masks.reshape(1, Ac).astype(i32),
    )
    nsyn = nsyn.reshape(-1)

    # --- [C, K, S]-scale epilogue (identical to the XLA tail): stamp
    # alloc + learned segments, then empty-segment death post-sweep ---
    sl = seg_last.reshape(-1)
    sl = jnp.where(alloc_seg.reshape(-1) | learn_trunc, it, sl)
    sl = jnp.where((sl >= 0) & (nsyn == 0), -1, sl)

    # same capacity-overflow accounting as the workspace path: truncated
    # active set, truncated prev-active packing, or > learn_cap learners
    overflow = (
        (active_cols.sum() > Ac) | (p_cols > Ac) | (ws_learn.sum() > L)
    )
    return presyn_n, perm_n, sl, conn.reshape(-1), pot.reshape(-1), overflow
