"""Model state initialization — shared by the CPU oracle and the TPU kernels.

The reference's state lives in C++ object graphs (SpatialPooler members,
Connections' segment/synapse lists — SURVEY.md C3/C5). Here all state is a
flat dict of fixed-shape numpy arrays, initialized once on host; the TPU
backend `device_put`s the very same arrays. Using one init for both backends
makes oracle-vs-TPU parity exact (SURVEY.md §4 item 2).

Layout (single stream; stream groups add a leading G axis):

SP state — two structurally different pool layouts (SPConfig.sparse_pool):

  dense (default; NuPIC-shaped):
    potential   bool [C, n_in]   fixed potential pool mask
    perm        P_sp [C, n_in]   permanences (0 outside potential)
  sparse (ISSUE 18; member-index pools):
    members     i16/i32 [C, P]   presynaptic INPUT indices of each column's
                                 P potential synapses, ascending; -1 = empty
                                 slot (only dense->sparse migration pads —
                                 models/migrate.py; i16 iff n_in fits)
    perm        P_sp [C, P]      permanences per member slot (0 in empty)
  shared:
    boost       f32  [C]         boost factors (1.0 when boost_strength == 0)
    overlap_duty f32 [C]         overlap duty cycles
    active_duty f32  [C]         activation duty cycles
    sp_iter     i32  []          records seen

TM state (dense bounded pools; C cols x K cells x S segments x M synapses):
    presyn      i16/i32 [C,K,S,M] presynaptic flat cell id, -1 = empty slot
                                 (i16 iff C*K <= 2^15 - 1)
    syn_perm    P_tm [C,K,S,M]   synapse permanences (0 in empty slots)
    seg_last    i32 [C,K,S]      last-used iteration, -1 = segment free (LRU key)
    active_seg  bool [C,K,S]     segments active at end of previous step
    matching_seg bool [C,K,S]    segments matching at end of previous step
    seg_pot     i16 [C,K,S]      active-potential synapse count at prev step
                                 (<= max_synapses_per_segment)
    prev_active bool [C,K]       active cells at previous step
    prev_winner bool [C,K]       winner cells at previous step
    tm_iter     i32  []

P_sp / P_tm are the permanence storage dtypes of the configured domains
(models/perm.py): f32 at perm_bits=0, uint16/uint8 fixed-point quanta
otherwise. The per-stream byte budget — the binding constraint at 100k
streams (SURVEY.md §7 hard part 4) — is computed honestly by
:func:`state_nbytes`, which sums the actual arrays.

Encoder state:
    enc_offset  f32 [n_fields]   RDSE offset, bound to first seen value
    enc_bound   bool []          whether offset has been bound
    enc_resolution f32 [n_fields] RDSE resolution (runtime, so one compiled
                                 program serves streams with different value
                                 ranges, e.g. a batched NAB corpus run)
"""

from __future__ import annotations

import numpy as np

from rtap_tpu.config import ModelConfig
from rtap_tpu.models.perm import sp_domain, tm_domain


def presyn_dtype(cfg: ModelConfig):
    """int16 whenever every cell id (< num_cells) fits, else int32. The -1
    empty-slot sentinel needs a signed type either way."""
    return np.int16 if cfg.num_cells <= (1 << 15) - 1 else np.int32


def members_dtype(cfg: ModelConfig):
    """Sparse SP member-index dtype: int16 whenever every input index
    (< input_size) fits, else int32 — same rule (and same -1 sentinel
    need) as presyn_dtype."""
    return np.int16 if cfg.input_size <= (1 << 15) - 1 else np.int32


def init_state(
    cfg: ModelConfig, seed: int = 0, include_fwd: bool = False,
    predict_horizon: int = 0,
) -> dict[str, np.ndarray]:
    """Build the full per-stream state dict (see module docstring for layout).

    `include_fwd` has no effect: the forward synapse index it asked for is
    gone (PR 29); the keyword stays because tests/benchmark passes False
    (ROADMAP D2b), and True raises.

    `predict_horizon` > 0 adds the predictive-horizon leaves (ISSUE 16,
    ops/predict_tpu.py): a k-deep ring of predicted-active column sets, the
    divergence EWMA, and the per-stream warm-up epoch. 0 (the default) omits
    them entirely, so predict-less state trees — and their checkpoints — stay
    byte-identical to pre-predict builds (the flags-off bit-exactness pin)."""
    if include_fwd:
        raise ValueError("init_state(include_fwd=True): no state carries a forward index")
    rng = np.random.Generator(np.random.Philox(key=(seed, 0xC0FFEE)))
    C, n_in = cfg.sp.columns, cfg.input_size
    K, S, M = cfg.tm.cells_per_column, cfg.tm.max_segments_per_cell, cfg.tm.max_synapses_per_segment

    if cfg.sp.sparse_pool:
        # Sparse member-index pool (ISSUE 18): exactly P distinct input
        # indices per column (a uniform P-subset via argsort of iid
        # uniforms), stored ascending. Every init slot is valid; -1 padding
        # only enters via dense->sparse migration (models/migrate.py).
        P = cfg.sp_members
        sel = np.argsort(rng.random((C, n_in)), axis=1, kind="stable")[:, :P]
        # Permanences seeded around the connected threshold so ~half the
        # pool starts connected (NuPIC's init strategy, SURVEY.md C3) —
        # the same formula as the dense branch, over member slots only.
        perm = np.clip(
            cfg.sp.syn_perm_connected + (rng.random((C, P)) - 0.5) * 0.1, 0.0, 1.0
        ).astype(np.float32)
        sp_pool = {
            "members": np.sort(sel, axis=1).astype(members_dtype(cfg)),  # rtap: partition[shard-streams]
            "perm": sp_domain(cfg.sp).quantize_init(perm),  # rtap: partition[shard-streams]
        }
    else:
        potential = rng.random((C, n_in)) < cfg.sp.potential_pct
        # Permanences seeded around the connected threshold so ~half the potential
        # pool starts connected (NuPIC's init strategy, SURVEY.md C3).
        perm = np.where(
            potential,
            np.clip(cfg.sp.syn_perm_connected + (rng.random((C, n_in)) - 0.5) * 0.1, 0.0, 1.0),
            0.0,
        ).astype(np.float32)
        sp_pool = {
            "potential": np.asarray(potential),  # rtap: partition[shard-streams]
            "perm": sp_domain(cfg.sp).quantize_init(perm),  # rtap: partition[shard-streams]
        }

    # Partition rules (ISSUE 15, rtap-lint partition-contract): every
    # leaf below is per-stream state whose group form carries a leading
    # G axis — shard-streams, the SDR-independence property ROADMAP-1's
    # mesh stands on. A future leaf that is NOT per-stream must declare
    # replicated/host-only or the analyzer refuses it.
    return {
        # SP pool (dense potential/perm or sparse members/perm — above)
        **sp_pool,
        "boost": np.ones(C, np.float32),  # rtap: partition[shard-streams]
        "overlap_duty": np.zeros(C, np.float32),  # rtap: partition[shard-streams]
        "active_duty": np.zeros(C, np.float32),  # rtap: partition[shard-streams]
        "sp_iter": np.int32(0),  # rtap: partition[shard-streams]
        # TM
        "presyn": np.full((C, K, S, M), -1, presyn_dtype(cfg)),  # rtap: partition[shard-streams]
        "syn_perm": np.zeros((C, K, S, M), tm_domain(cfg.tm).dtype),  # rtap: partition[shard-streams]
        "seg_last": np.full((C, K, S), -1, np.int32),  # rtap: partition[shard-streams]
        "active_seg": np.zeros((C, K, S), bool),  # rtap: partition[shard-streams]
        "matching_seg": np.zeros((C, K, S), bool),  # rtap: partition[shard-streams]
        "seg_pot": np.zeros((C, K, S), np.int16),  # rtap: partition[shard-streams]
        "prev_active": np.zeros((C, K), bool),  # rtap: partition[shard-streams]
        "prev_winner": np.zeros((C, K), bool),  # rtap: partition[shard-streams]
        "tm_iter": np.int32(0),  # rtap: partition[shard-streams]
        # device-kernel capacity overflow counter
        "tm_overflow": np.int32(0),  # rtap: partition[shard-streams]

        # encoder (offset binds per field at the first *finite* value seen;
        # resolutions are per field — uniform configs repeat the family
        # default bit-for-bit, composite fields carry their FieldSpec's)
        "enc_offset": np.zeros(cfg.n_fields, np.float32),  # rtap: partition[shard-streams]
        "enc_bound": np.zeros(cfg.n_fields, bool),  # rtap: partition[shard-streams]
        "enc_resolution": np.asarray(cfg.field_resolutions(), np.float32),  # rtap: partition[shard-streams]
        # delta-encoder predecessor (composite family only): last FINITE
        # value per field, NaN = no predecessor yet (the first sample of
        # a delta field encodes as missing — NuPIC DeltaEncoder). Absent
        # for every non-delta config, so pre-ISSUE-9 state trees (and
        # their checkpoints) are byte-identical.
        **({"enc_prev": np.full(cfg.n_fields, np.nan, np.float32)}  # rtap: partition[shard-streams]
           if cfg.composite is not None and cfg.composite.has_delta else {}),
        # predictive-horizon leaves (ISSUE 16, ops/predict_tpu.py): present
        # only when a horizon is armed — serve --predict off keeps the tree
        # byte-identical to HEAD. pred_ring slot t%k holds the predicted-
        # active column set captured at tick t; pred_miss_ewma is NaN until
        # the stream's first scored tick; pred_tick0 is the (re)init tick —
        # claimed slots stay unscored for a full horizon (registry sets it).
        **({
            "pred_ring": np.zeros((predict_horizon, cfg.sp.columns), bool),  # rtap: partition[shard-streams]
            "pred_miss_ewma": np.float32(np.nan),  # rtap: partition[shard-streams]
            "pred_tick0": np.int32(0),  # rtap: partition[shard-streams]
        } if predict_horizon else {}),
        # SDR classifier (SURVEY.md C10), present only when enabled
        **(
            {
                "cls_w": np.zeros((C * K, cfg.classifier.buckets), np.float32),  # rtap: partition[shard-streams]
                "cls_val": np.zeros(cfg.classifier.buckets, np.float32),  # rtap: partition[shard-streams]
                "cls_cnt": np.zeros(cfg.classifier.buckets, np.int32),  # rtap: partition[shard-streams]
            }
            if cfg.classifier.enabled
            else {}
        ),
    }


def state_nbytes(cfg: ModelConfig, seed: int = 0) -> dict[str, int]:
    """Honest per-stream device-state byte budget: sums the actual arrays of
    one stream's state (the authoritative number for SCALING.md and the
    preset docstrings; a hand-derived figure in round 2 was off by 9x).

    Returns {"total": bytes, "<key>": bytes, ...} sorted descending by size.
    """
    st = init_state(cfg, seed)
    per = {k: int(np.asarray(v).nbytes) for k, v in st.items()}
    out = {"total": sum(per.values())}
    out.update(sorted(per.items(), key=lambda kv: -kv[1]))
    return out
