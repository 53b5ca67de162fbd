"""Spatial Pooler — numpy oracle.

Semantics per SURVEY.md C3 / §3.2 (NuPIC `spatial_pooler.py` +
`SpatialPooler.cpp`): overlap = connected-synapse count on active inputs,
boosting, global k-winner inhibition, Hebbian permanence learning, duty
cycles with weak-column permanence bump.

Deviations from NuPIC, deliberate and shared with the TPU kernel so both
backends agree bit-for-bit:
- top-k tie-break is deterministic by lower column index (NuPIC breaks ties
  by internal ordering of its sort) — encoded as score = overlap*C + (C-1-c);
- the weak-column bump (raisePermanenceToThreshold) applies every step via
  duty-cycle comparison rather than NuPIC's every-50-step update period.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.config import SPConfig
from benchmark.reference.perm import sp_domain


def sp_overlap(state: dict, input_sdr: np.ndarray, cfg: SPConfig) -> np.ndarray:
    """Overlap per column: number of connected potential synapses whose
    presynaptic input bit is active.

    Dense layout: indexes the ~w active bits instead of building the full
    [C, n_in] connected mask (O(C*w) vs O(C*n_in)). Sparse layout
    (SPConfig.sparse_pool, ISSUE 18): gathers the SDR at the member-index
    table [C, P] and counts connected hits (O(C*P)); empty slots
    (members == -1) are masked out, clamp-gathered in-bounds exactly like
    the device kernel. Exact integer counts either way."""
    connected = sp_domain(cfg).threshold(cfg.syn_perm_connected)
    if cfg.sparse_pool:
        members = state["members"]
        hit = input_sdr[np.maximum(members, 0)]
        cols = (state["perm"] >= connected) & (members >= 0) & hit
        return cols.sum(1, dtype=np.int64)
    idx = np.nonzero(input_sdr)[0]
    if len(idx) == 0:
        return np.zeros(state["perm"].shape[0], np.int64)
    cols = (state["perm"][:, idx] >= connected) & state["potential"][:, idx]
    return cols.sum(1, dtype=np.int64)


def sp_inhibit(overlap: np.ndarray, boost: np.ndarray, cfg: SPConfig) -> np.ndarray:
    """Global k-winner inhibition -> bool[C] active columns.

    Winners are the top `num_active_columns` by boosted overlap with
    deterministic low-index tie-break; columns below stimulus_threshold
    (on raw overlap) never win.
    """
    C = overlap.shape[0]
    if cfg.boost_strength > 0.0:
        # Quantize boosted overlap to 1/256 so the low-index tie-break term
        # can never override a real (>= 1/256) difference. Note this makes
        # host/device winner parity overwhelmingly likely but not guaranteed:
        # a 1-ulp exp() difference can still flip q on an exact .5 boundary.
        # The NAB preset runs boost_strength=0, where parity is exact.
        # same f32 clamp as the device kernel, BEFORE the int cast: i64
        # cannot wrap here, but the DEVICE computes this score in i32
        # and clamps q (in f32 — an overflowing f32→i32 convert is
        # backend-defined) to keep q*C + tiebreak < 2^31; the min(·,
        # 2^24) keeps qmax f32-exact for C < 128 (see ops/sp_tpu.py).
        # The oracle mirrors the exact expression so the twins stay
        # bit-identical even under pathological boost (ISSUE 14).
        qmax = np.float32(min((2**31 - C) // C, 2**24))
        qf = np.round((overlap * boost).astype(np.float32) * 256.0)
        q = np.clip(qf, np.float32(0.0), qmax).astype(np.int64)
        score = q * C + (C - 1 - np.arange(C))
    else:
        score = overlap.astype(np.int64) * C + (C - 1 - np.arange(C))
    k = cfg.num_active_columns
    winners = np.argsort(score)[::-1][:k]
    active = np.zeros(C, bool)
    active[winners] = True
    active &= overlap >= cfg.stimulus_threshold
    return active


def sp_learn(
    state: dict, input_sdr: np.ndarray, overlap: np.ndarray, active: np.ndarray, cfg: SPConfig
) -> None:
    """Hebbian update on winners + duty cycles + boost + weak-column bump.

    `overlap` is this step's pre-learning overlap (duty cycles measure what
    the column saw, not what it would see after the update). Mutates `state`
    in place (the oracle is imperative; the TPU kernel is the functional twin).
    """
    dom = sp_domain(cfg)
    if cfg.sparse_pool:
        # sparse member-index pool: the valid mask (members >= 0) plays the
        # dense potential mask's role, and the per-slot SDR bit comes from
        # the member gather — same masks, same op order as the device twin
        members = state["members"]
        potential = members >= 0
        hit = input_sdr[np.maximum(members, 0)]
        inc_mask = active[:, None] & potential & hit
        dec_mask = active[:, None] & potential & ~hit
    else:
        potential = state["potential"]
        inc_mask = active[:, None] & potential & input_sdr[None, :]
        dec_mask = active[:, None] & potential & ~input_sdr[None, :]
    # Arithmetic runs in the domain's compute dtype. f32 domain: np.float32
    # constants (a python float * bool-mask would promote to f64 and
    # double-round on the store, drifting 1 ulp from the device f32 chain —
    # see temporal_memory._reinforce_and_grow). Quantized domain: int32, so
    # adds can't wrap the narrow storage type before the clip.
    perm = state["perm"].astype(dom.compute_dtype)
    perm += dom.rate(cfg.syn_perm_active_inc) * inc_mask
    perm -= dom.rate(cfg.syn_perm_inactive_dec) * dec_mask
    np.clip(perm, dom.zero, dom.one, out=perm)

    it = int(state["sp_iter"]) + 1
    state["sp_iter"] = np.int32(it)
    period = np.float32(min(cfg.duty_cycle_period, it))
    overlap_now = (overlap > 0).astype(np.float32)
    # Moving average in incremental form d += (x-d)/p, not (d*(p-1)+x)/p: the
    # latter's multiply-add gets FMA-contracted by XLA on device (1-ulp drift
    # vs numpy, observed); sub/div/add has no contractable pattern, so host
    # and device stay bit-identical.
    state["overlap_duty"] = state["overlap_duty"] + (overlap_now - state["overlap_duty"]) / period
    state["active_duty"] = state["active_duty"] + (
        active.astype(np.float32) - state["active_duty"]
    ) / period

    if cfg.boost_strength > 0.0:
        target = cfg.num_active_columns / perm.shape[0]
        state["boost"] = np.exp((target - state["active_duty"]) * cfg.boost_strength).astype(np.float32)

    # Bump starved columns: below min_pct of the max overlap duty cycle ->
    # raise all potential permanences (keeps dead columns recoverable).
    min_duty = cfg.min_pct_overlap_duty_cycle * state["overlap_duty"].max()
    weak = state["overlap_duty"] < min_duty
    if weak.any():
        perm += dom.rate(cfg.syn_perm_below_stimulus_inc) * (weak[:, None] & potential)
        np.clip(perm, dom.zero, dom.one, out=perm)
    state["perm"] = perm.astype(dom.dtype)


def sp_compute(state: dict, input_sdr: np.ndarray, cfg: SPConfig, learn: bool = True) -> np.ndarray:
    """One SP step -> bool[C] active columns. Mutates state if learn."""
    overlap = sp_overlap(state, input_sdr, cfg)
    active = sp_inhibit(overlap, state["boost"], cfg)
    if learn:
        sp_learn(state, input_sdr, overlap, active, cfg)
    return active
