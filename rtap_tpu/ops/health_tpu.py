"""Fused on-device model-health reducers (ISSUE 6 tentpole).

The serve stack can see its own latency (obs/trace.py) and durability
(resilience/journal.py) but the MODEL is a black box while serving:
nothing reports segment-pool occupancy, permanence distributions, SDR
sparsity, or prediction accuracy. SDR theory (PAPERS.md, 1503.07469)
says capacity and robustness live in exactly those quantities — a
collapsed active-column sparsity or a saturated segment pool is a
detector-quality incident even when every tick hits its deadline — and
ROADMAP item 3 (segment-pool right-sizing from live fleet occupancy)
needs the numbers this module produces.

:func:`health_reduce` runs INSIDE the fused step program (ops/step.py
`_tick`, behind the static ``health`` flag): it reads the post-step
state the scan already holds on device and reduces it to one small
per-group leaf (~200 bytes — a handful of scalars plus three fixed-bin
histograms), returned alongside the scores. Properties the tests pin:

- **Pure reads.** The model state, scores, and alert stream are
  bit-identical with health on vs off
  (tests/integration/test_health_serve.py).
- **No extra device<->host state fetch.** The leaf rides the existing
  chunk output; the host never pulls pool tensors.
- **Bounded size.** Histogram bin counts are module constants, so the
  leaf is a few hundred bytes per group regardless of G or model width.

Aggregation semantics: per-stream fractions are averaged over the LIVE
streams of the tick (streams whose polled values had at least one
finite field) — pad slots and silent streams must not dilute a
half-full group's occupancy story. Pool-wide quantities are reduced as
per-stream fractions (mean over live streams), never as raw counts: a
group-level synapse count at 100k-stream scale overflows int32 and f32
alike, a mean fraction never does.

:func:`health_reduce_host` is the bit-twin on numpy/public-layout
state — the CPU-oracle backend's health path and the parity oracle for
the device reducer (tests/unit/test_health.py).
"""

from __future__ import annotations

import numpy as np

from rtap_tpu.config import ModelConfig
from rtap_tpu.models.perm import tm_domain

__all__ = [
    "HEALTH_KEYS",
    "OCC_BINS",
    "PERM_BINS",
    "SCORE_BINS",
    "health_nbytes",
    "health_reduce",
    "health_reduce_host",
    "health_from_states",
]

#: per-stream segment-pool occupancy fraction histogram bins (streams
#: are counted into bins of used-segment fraction — the right-sizing
#: evidence: a fleet living in the top bin needs a bigger pool, one in
#: the bottom bins is paying HBM for nothing)
OCC_BINS = 8

#: permanence-distribution sketch bins over the [0, 1] domain (counts
#: are per-stream-normalized then averaged, so the sketch is a
#: probability vector once any synapse exists)
PERM_BINS = 8

#: streaming anomaly-score histogram bins over [0, 1] — the host-side
#: EWMA drift detector (obs/health.py) folds these per tick
SCORE_BINS = 16

#: the leaf's key set, in a fixed order (schema contract for the host
#: tracker, the /health route, and the drift gate tests)
HEALTH_KEYS = (
    "occ_hist",        # i32 [OCC_BINS]  live streams per occupancy bin
    "seg_occ_frac",    # f32 []  mean used-segment fraction (live streams)
    "syn_frac",        # f32 []  mean non-empty synapse-slot fraction
    "perm_hist",       # f32 [PERM_BINS] mean normalized permanence sketch
    "perm_conn_frac",  # f32 []  mean connected fraction among non-empty
    "act_col_frac",    # f32 []  mean active-column fraction (of C)
    "pred_cell_frac",  # f32 []  mean predictive-cell fraction (of C*K)
    "hit_num",         # f32 []  sum of (1 - raw) * active_cols (scored)
    "hit_den",         # f32 []  sum of active_cols (scored streams)
    "score_hist",      # i32 [SCORE_BINS] scored streams per raw-score bin
    "scored",          # i32 []  streams scored this tick (live, finite raw)
)


def health_nbytes() -> int:
    """Bytes per (group, tick) health leaf — the "few hundred bytes"
    bound the module docstring claims, computed from the schema."""
    return 4 * (OCC_BINS + PERM_BINS + SCORE_BINS
                + len(HEALTH_KEYS) - 3)


def health_reduce(state: dict, raw, values, cfg: ModelConfig) -> dict:
    """Per-group health aggregates from POST-STEP group state (device).

    `state` is the kernel-layout group state ([G, ...] leaves — flat or
    aos, the reductions are layout-invariant), `raw` the [G] raw anomaly
    scores of the tick, `values` the [G, n_fields] polled inputs (the
    live-stream mask source). Pure: reads only, returns a fresh dict of
    small arrays (see :data:`HEALTH_KEYS`). Traced inside the fused step
    program — keep everything shape-static and reduction-only.
    """
    import jax.numpy as jnp

    tm = cfg.tm
    C, K, S = cfg.sp.columns, tm.cells_per_column, tm.max_segments_per_cell
    G = state["seg_last"].shape[0]

    liv = jnp.isfinite(values).any(-1)  # [G] streams with data this tick
    livf = liv.astype(jnp.float32)
    n_live = jnp.maximum(livf.sum(), 1.0)

    # -- segment-pool occupancy (ROADMAP-3 right-sizing evidence) --
    seg_axes = tuple(range(1, state["seg_last"].ndim))
    seg_used = (state["seg_last"] >= 0).sum(seg_axes)  # [G] i32
    seg_cap = float(np.prod(state["seg_last"].shape[1:]))
    occ = seg_used.astype(jnp.float32) / seg_cap  # [G]
    occ_bin = jnp.clip((occ * OCC_BINS).astype(jnp.int32), 0, OCC_BINS - 1)
    occ_hist = ((occ_bin[:, None] == jnp.arange(OCC_BINS)[None, :])
                & liv[:, None]).sum(0).astype(jnp.int32)
    seg_occ_frac = (occ * livf).sum() / n_live

    # -- synapse pool + permanence sketch --
    pool_axes = tuple(range(1, state["presyn"].ndim))
    used_syn = state["presyn"] >= 0
    syn_used = used_syn.sum(pool_axes).astype(jnp.float32)  # [G]
    pool_cap = float(np.prod(state["presyn"].shape[1:]))
    syn_frac = (syn_used / pool_cap * livf).sum() / n_live
    dom = tm_domain(tm)
    perm_f = state["syn_perm"].astype(jnp.float32)
    pbin = jnp.clip((perm_f / jnp.float32(dom.one)
                     * PERM_BINS).astype(jnp.int32), 0, PERM_BINS - 1)
    denom = jnp.maximum(syn_used, 1.0)
    per_bin = jnp.stack(
        [((pbin == b) & used_syn).sum(pool_axes).astype(jnp.float32)
         for b in range(PERM_BINS)], axis=-1)  # [G, PERM_BINS]
    perm_hist = (per_bin / denom[:, None] * livf[:, None]).sum(0) / n_live
    conn_thr = jnp.float32(dom.threshold(tm.connected_permanence))
    conn = ((perm_f >= conn_thr) & used_syn).sum(pool_axes).astype(jnp.float32)
    perm_conn_frac = (conn / denom * livf).sum() / n_live

    # -- SDR sparsity (post-step prev_active = THIS tick's active cells;
    #    post-step active_seg = the dendrites predicting t+1) --
    ac = state["prev_active"].any(-1).sum(-1).astype(jnp.float32)  # [G]
    act_col_frac = (ac / float(C) * livf).sum() / n_live
    aseg = state["active_seg"].reshape(G, C, K, S)
    pred_cells = aseg.any(-1).sum((-1, -2)).astype(jnp.float32)  # [G]
    pred_cell_frac = (pred_cells / float(C * K) * livf).sum() / n_live

    # -- predicted->active hit rate + streaming score histogram --
    rawc = jnp.clip(jnp.nan_to_num(raw, nan=0.0), 0.0, 1.0)
    rfin = jnp.isfinite(raw) & liv
    rfinf = rfin.astype(jnp.float32)
    hit_num = (rfinf * (1.0 - rawc) * ac).sum()
    hit_den = (rfinf * ac).sum()
    sbin = jnp.clip((rawc * SCORE_BINS).astype(jnp.int32), 0, SCORE_BINS - 1)
    score_hist = ((sbin[:, None] == jnp.arange(SCORE_BINS)[None, :])
                  & rfin[:, None]).sum(0).astype(jnp.int32)

    return {
        "occ_hist": occ_hist,
        "seg_occ_frac": seg_occ_frac,
        "syn_frac": syn_frac,
        "perm_hist": perm_hist,
        "perm_conn_frac": perm_conn_frac,
        "act_col_frac": act_col_frac,
        "pred_cell_frac": pred_cell_frac,
        "hit_num": hit_num,
        "hit_den": hit_den,
        "score_hist": score_hist,
        "scored": rfin.sum().astype(jnp.int32),
    }


def health_reduce_host(state: dict, raw: np.ndarray, values: np.ndarray,
                       cfg: ModelConfig) -> dict:
    """Numpy twin of :func:`health_reduce` on PUBLIC-layout group state
    ([G, C, K, S, M] pools — what ``grp.state`` reads as between chunks).
    Same schema, same semantics; the parity test pins the two against
    each other and the CPU-oracle backend emits health through it."""
    tm = cfg.tm
    C, K, S = cfg.sp.columns, tm.cells_per_column, tm.max_segments_per_cell
    G = np.shape(state["seg_last"])[0]
    values = np.asarray(values, np.float32)
    if values.ndim == 1:
        values = values[:, None]
    raw = np.asarray(raw, np.float32)

    liv = np.isfinite(values).any(-1)
    livf = liv.astype(np.float32)
    n_live = max(float(livf.sum()), 1.0)

    seg_last = np.asarray(state["seg_last"]).reshape(G, -1)
    seg_used = (seg_last >= 0).sum(-1)
    occ = seg_used.astype(np.float32) / float(seg_last.shape[1])
    occ_bin = np.clip((occ * OCC_BINS).astype(np.int32), 0, OCC_BINS - 1)
    occ_hist = ((occ_bin[:, None] == np.arange(OCC_BINS)[None, :])
                & liv[:, None]).sum(0).astype(np.int32)

    presyn = np.asarray(state["presyn"]).reshape(G, -1)
    used_syn = presyn >= 0
    syn_used = used_syn.sum(-1).astype(np.float32)
    syn_frac = float((syn_used / presyn.shape[1] * livf).sum() / n_live)
    dom = tm_domain(tm)
    perm_f = np.asarray(state["syn_perm"]).reshape(G, -1).astype(np.float32)
    pbin = np.clip((perm_f / np.float32(dom.one)
                    * PERM_BINS).astype(np.int32), 0, PERM_BINS - 1)
    denom = np.maximum(syn_used, 1.0)
    per_bin = np.stack(
        [((pbin == b) & used_syn).sum(-1).astype(np.float32)
         for b in range(PERM_BINS)], axis=-1)
    perm_hist = ((per_bin / denom[:, None] * livf[:, None]).sum(0)
                 / n_live).astype(np.float32)
    conn_thr = np.float32(dom.threshold(tm.connected_permanence))
    conn = ((perm_f >= conn_thr) & used_syn).sum(-1).astype(np.float32)
    perm_conn_frac = float((conn / denom * livf).sum() / n_live)

    ac = np.asarray(state["prev_active"]).any(-1).sum(-1).astype(np.float32)
    act_col_frac = float((ac / float(C) * livf).sum() / n_live)
    aseg = np.asarray(state["active_seg"]).reshape(G, C, K, S)
    pred_cells = aseg.any(-1).sum((-1, -2)).astype(np.float32)
    pred_cell_frac = float((pred_cells / float(C * K) * livf).sum() / n_live)

    rawc = np.clip(np.nan_to_num(raw, nan=0.0), 0.0, 1.0)
    rfin = np.isfinite(raw) & liv
    rfinf = rfin.astype(np.float32)
    sbin = np.clip((rawc * SCORE_BINS).astype(np.int32), 0, SCORE_BINS - 1)
    score_hist = ((sbin[:, None] == np.arange(SCORE_BINS)[None, :])
                  & rfin[:, None]).sum(0).astype(np.int32)

    return {
        "occ_hist": occ_hist,
        "seg_occ_frac": np.float32((occ * livf).sum() / n_live),
        "syn_frac": np.float32(syn_frac),
        "perm_hist": perm_hist,
        "perm_conn_frac": np.float32(perm_conn_frac),
        "act_col_frac": np.float32(act_col_frac),
        "pred_cell_frac": np.float32(pred_cell_frac),
        "hit_num": np.float32((rfinf * (1.0 - rawc) * ac).sum()),
        "hit_den": np.float32((rfinf * ac).sum()),
        "score_hist": score_hist,
        "scored": np.int32(rfin.sum()),
    }


def health_from_states(states: list[dict], raw: np.ndarray,
                       values: np.ndarray, cfg: ModelConfig) -> dict:
    """CPU-oracle backend adapter: stack per-stream oracle state dicts
    into a [G, ...] view and reduce through the host twin. Only the
    leaves the reducer reads are stacked (views where possible)."""
    grouped = {
        k: np.stack([np.asarray(s[k]) for s in states])
        for k in ("seg_last", "presyn", "syn_perm", "prev_active",
                  "active_seg")
    }
    return health_reduce_host(grouped, raw, values, cfg)
