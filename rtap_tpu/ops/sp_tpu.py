"""Spatial Pooler — device kernel (functional twin of oracle/spatial_pooler.py).

The reference's SP hot loop is SpatialPooler.cpp's sparse matvec + inhibition
(SURVEY.md C3, §3.2). Two TPU-native pool layouts (SPConfig.sparse_pool):

* dense (default): the connected-synapse mask is a dense bool [C, n_in];
  overlap is a 0/1 matmul that XLA tiles onto the MXU (counts < 2^24, so f32
  accumulation is exact).
* sparse (ISSUE 18): the pool is a member-index table [C, P] of input
  indices (-1 = empty slot) + perm [C, P]; overlap tests each member's bit
  in the SDR packed into uint32 words (`_sdr_at_members`: selects and
  shifts on the VPU, no gather) and reduces over the P lane — an O(C*P)
  test-and-count instead of the O(C*n_in) matmul, and the learning pass
  sweeps C*P instead of C*n_in permanence slots. On a memory-bound step the
  byte traffic, not the flop count, is the cost (docs/KERNELS.md roofline
  section), so shrinking the swept plane is both the HBM and the
  throughput lever. Counts stay exact integers on both layouts.

Inhibition is `lax.top_k` over an integer score that encodes the low-index
tie-break, making winner selection bit-identical to the oracle's argsort on
either layout.

State dict keys/layout are shared with the oracle (models/state.py); this
module never mutates — it returns the updated SP slice of the state dict.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from rtap_tpu.config import SPConfig
from rtap_tpu.models.perm import sp_domain


def _sdr_at_members(pool: jnp.ndarray, sdr: jnp.ndarray) -> jnp.ndarray:
    """SDR bits at each member slot: bool [C, P] = sdr[pool], with no gather.

    The SDR is packed once into W = ceil(n_in / 32) uint32 words; each slot
    picks its word (index >> 5) through a chain of W - 1 selects against the
    stream's scalars and tests bit (index & 31) — a dozen integer VPU ops an
    element at W = 4 that XLA fuses into the caller's pass over [C, P]. An
    element-wise XLA gather of the same bits ran at ~10 ns an element on a
    v5e (docs/KERNELS.md). W is static, so one form serves every n_in.
    Empty slots (-1) read index 0 and are masked out by every caller via
    ``pool >= 0``."""
    n_in = sdr.shape[0]
    n_words = -(-n_in // 32)
    bits = jnp.pad(sdr, (0, n_words * 32 - n_in)).reshape(n_words, 32).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=1, dtype=jnp.uint32)
    idx = jnp.maximum(pool, 0).astype(jnp.int32)
    word_of = idx >> 5
    word = words[n_words - 1]
    for j in range(n_words - 2, -1, -1):
        word = jnp.where(word_of == j, words[j], word)
    return ((word >> (idx & 31).astype(jnp.uint32)) & 1).astype(bool)


# rtap: twin[sp_overlap] — explicit-tensor calling convention vs the
# oracle's state-dict one; same math, parity in test_twin_registry.py
def sp_overlap(perm: jnp.ndarray, pool: jnp.ndarray, sdr: jnp.ndarray, cfg: SPConfig) -> jnp.ndarray:
    """Overlap per column = |connected potential synapses ∩ active inputs|.

    `pool` is the layout-defining tensor: dense bool potential mask
    [C, n_in], or the sparse member-index table [C, P]. Exact integer
    counts either way (dense: 0/1 f32 matmul -> MXU; sparse: bit test +
    masked popcount on the VPU)."""
    thr = sp_domain(cfg).threshold(cfg.syn_perm_connected)
    if cfg.sparse_pool:
        connected = (perm >= thr) & (pool >= 0)
        hit = _sdr_at_members(pool, sdr)
        return jnp.sum((connected & hit).astype(jnp.int32), axis=1)
    connected = ((perm >= thr) & pool).astype(jnp.float32)
    return jnp.dot(connected, sdr.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)


def sp_inhibit(overlap: jnp.ndarray, boost: jnp.ndarray, cfg: SPConfig) -> jnp.ndarray:
    """Global k-winner inhibition -> bool[C]. Score = overlap*C + (C-1-c)
    (quantized to 1/256 under boosting) is unique per column, so top_k has no
    ties and matches the oracle's descending argsort exactly when
    boost_strength == 0 (the NAB preset). Under boosting, a 1-ulp host/device
    exp() difference on an exact .5 rounding boundary of q can still flip a
    winner — statistically negligible, and tolerated by the boost parity test.

    The winner mask is `score >= (the least of the k largest scores)`:
    the scores are distinct, so exactly the k columns top_k picked pass. That
    one [C] compare stands where the k winner indices were written into a
    zero mask (an element-wise scatter, 5-7 ns an update on a v5e; PERF.md
    s6 PR 31) and is cheaper than the [k, C] index compare, since top_k's
    values are there anyway and its indices are no longer needed.
    """
    C = overlap.shape[0]
    col_rev = (C - 1 - jnp.arange(C, dtype=jnp.int32))
    if cfg.boost_strength > 0.0:
        # q*C + col_rev must stay < 2^31: the device computes the score
        # in i32 while the host oracle widens to i64, so an unclamped q
        # (pathological boost × overlap > ~8M/C) would WRAP here and
        # invert winners on TPU only. Both twins clamp IN F32, BEFORE
        # the int cast — an out-of-range f32→i32 convert is backend-
        # defined, so clamping after it would rest on exactly the
        # nonportability this guards against. The extra min(·, 2^24)
        # keeps qmax f32-EXACT for every C: for C < 128 the raw bound
        # exceeds 2^24 and float32() would round it UP (C=64 →
        # 33554431 → 2^25), re-opening the wrap; capped at 2^24 the
        # compare and casts are exact and q*C ≤ 2^24·C < 2^31
        # whenever the raw bound was the larger one. Twins stay
        # bit-identical in every regime (the ISSUE 14 dtype-domain
        # gate's i32-wrap rule pins this shape).
        qmax = jnp.float32(min((2**31 - C) // C, 2**24))
        qf = jnp.round(overlap.astype(jnp.float32) * boost * 256.0)
        q = jnp.clip(qf, 0.0, qmax).astype(jnp.int32)
        score = q * C + col_rev
    else:
        score = overlap * C + col_rev
    kth = jax.lax.top_k(score, cfg.num_active_columns)[0].min()
    return (score >= kth) & (overlap >= cfg.stimulus_threshold)


def sp_learn(
    state: dict, sdr: jnp.ndarray, overlap: jnp.ndarray, active: jnp.ndarray, cfg: SPConfig
) -> dict:
    """Hebbian update on winners + duty cycles + boost + weak-column bump.
    Same op order as the oracle (hebbian -> clip -> duty -> boost -> bump ->
    clip); inc/dec masks are disjoint so the fused expression is bit-equal to
    the oracle's sequential += / -=. Quantized domains compute in int32
    (bit-equal to the oracle's int32 by construction). Sparse layout: the
    per-slot SDR bit comes from `_sdr_at_members` and the valid mask
    (members >= 0) plays the dense potential mask's role in every term."""
    dom = sp_domain(cfg)
    if cfg.sparse_pool:
        pool = state["members"]
        valid = pool >= 0
        hit = _sdr_at_members(pool, sdr)
        inc_mask = active[:, None] & valid & hit
        dec_mask = active[:, None] & valid & ~hit
        bump_pool = valid
    else:
        pool = state["potential"]
        inc_mask = active[:, None] & pool & sdr[None, :]
        dec_mask = active[:, None] & pool & ~sdr[None, :]
        bump_pool = pool
    perm = state["perm"].astype(dom.compute_dtype)
    perm = perm + dom.rate(cfg.syn_perm_active_inc) * inc_mask - dom.rate(cfg.syn_perm_inactive_dec) * dec_mask
    perm = jnp.clip(perm, dom.zero, dom.one)

    it = state["sp_iter"] + 1
    period = jnp.minimum(cfg.duty_cycle_period, it).astype(jnp.float32)
    overlap_now = (overlap > 0).astype(jnp.float32)
    # d += (x-d)/p form (not (d*(p-1)+x)/p): sub/div/add has no multiply-add
    # for XLA to FMA-contract, keeping device duty bit-identical to the numpy
    # oracle (an optimization_barrier does NOT stop the contraction; observed).
    overlap_duty = state["overlap_duty"] + (overlap_now - state["overlap_duty"]) / period
    active_duty = state["active_duty"] + (active.astype(jnp.float32) - state["active_duty"]) / period

    boost = state["boost"]
    if cfg.boost_strength > 0.0:
        target = cfg.num_active_columns / perm.shape[0]
        boost = jnp.exp((target - active_duty) * cfg.boost_strength).astype(jnp.float32)

    min_duty = cfg.min_pct_overlap_duty_cycle * overlap_duty.max()
    weak = overlap_duty < min_duty
    perm = jnp.clip(
        perm + dom.rate(cfg.syn_perm_below_stimulus_inc) * (weak[:, None] & bump_pool),
        dom.zero, dom.one,
    )

    return {
        **state,
        "perm": perm.astype(dom.dtype),
        "boost": boost,
        "overlap_duty": overlap_duty,
        "active_duty": active_duty,
        "sp_iter": it.astype(jnp.int32),
    }


# rtap: twin[sp_compute] — the oracle names the full SP step sp_compute
@partial(jax.jit, static_argnames=("cfg", "learn"))
def sp_step(state: dict, sdr: jnp.ndarray, cfg: SPConfig, learn: bool = True):
    """One SP step -> (new_state, bool[C] active columns). Pure."""
    pool = state["members"] if cfg.sparse_pool else state["potential"]
    # the scope names are the step's vocabulary (ops/step.py SCOPES)
    with jax.named_scope("rtap.sp.overlap"):
        overlap = sp_overlap(state["perm"], pool, sdr, cfg)
    with jax.named_scope("rtap.sp.inhibit"):
        active = sp_inhibit(overlap, state["boost"], cfg)
    if learn:
        with jax.named_scope("rtap.sp.learn"):
            state = sp_learn(state, sdr, overlap, active, cfg)
    return state, active
