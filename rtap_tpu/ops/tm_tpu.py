"""Temporal Memory — device kernel (functional twin of oracle/temporal_memory.py).

One rule for every kernel of the step: index lists become masks by compare
(against an iota, fused into whatever reads the mask), never by
`.at[ids].set`, and small tables are read through such a grid too, never by
`table[ids]`, because an element-wise gather or scatter costs 5-10 ns an
element on a v5e (PRs 26, 28, 31; PERF.md s6). Only whole pool rows move by
index (the wide-row form, `rtap.tm.learn.rows`), out of and into pools laid
out so that such a row is one contiguous block.

The reference's TM is Cells4.cpp/TemporalMemory.cpp over the Connections
pointer graph (SURVEY.md C4/C5). TPU-native re-design (SURVEY.md §7 hard part
1): fixed-capacity dense pools [C, K, S, M] of (presyn id, permanence), and a
step built around two column-compact structures (profiled on v5e: the flat
formulations in the first design cost 144 ms/tick at G=2048; these bring the
same semantics down by an order of magnitude):

1. **Packed-column membership.** "Is this synapse's presynaptic cell active?"
   Active cells can only live in active columns (<= col_cap of them, = SP's
   k winners), so the active set is (column ids [Ac], per-column K-bit cell
   masks [Ac]) instead of a flat cell-id list. Membership is a static chain
   of Ac compare + selects that picks the column's mask, then a bit probe —
   8-32x fewer VPU ops than the flat cell-id compare at preset sizes, no
   serialized gather, and element-wise from the pool to the bit, so it
   fuses into the one pass of whatever sweep asks (the punish sweep, the
   dendrite sweep; docs/KERNELS.md, "TM membership").
2. **Column-compact learning workspace.** Every learning segment lives in an
   active column, so the learning pass gathers the <= col_cap active columns
   into a [Ac, K*S*M] workspace (by one-hot moves over the whole pool at
   narrow pool rows — a compare-select reduce or an MXU matmul — by index
   at wide ones; below), does the compact reinforce/grow pass there
   (selecting <= learn_cap segments with a cheap top_k over Ac*K*S instead
   of C*K*S, where that cap cuts rows at all), and scatters the workspace
   back.

Step outline: dense column categorization (predicted / burst-matching /
burst-new) -> workspace learning (alloc, reinforce, grow toward previous
winner cells with weakest-synapse eviction) -> punishment of matching
segments in non-active columns -> synapse/segment death -> dendrite activity
for t+1. Tie-breaks are lowest-index everywhere, matching the oracle exactly;
parity is bit-for-bit (tests/parity/test_tm_parity.py).

The step has two forms and the static shape picks between them in one place
(:func:`wide_rows`): below WIDE_ROW_LANES synapse lanes a pool row, one-hot
MXU matmuls move the workspace's rows and the pools run flat ([C, K*S*M],
per-segment reductions as a block-diagonal matmul); at or above it, the rows
move by index and, in a scan over ticks, the pools run [C, M, K*S] — the
segments on the lanes, a segment's synapses down the sublanes, so a column's
row is contiguous for the moves AND the full-pool sweep has whole tiles on
both minor dims; one layout serves both and no pool changes layout inside
the scan (docs/KERNELS.md, "Why [C, M, K*S]"). The owners of a state — a
stream group, a step runner — keep it on the device in the kernel's form
between programs (:func:`resident_form`; ops/resident.py), so their programs
enter and leave no layout. A tree handed over in the public [C, K, S, M]
layout enters and leaves the kernel's once a program, whatever the
program's length (:func:`resident_form`, :func:`public_form`; ops/step.py
calls them under `rtap.layout`). The form is a function of the shape, and
nothing outside this module and ops/resident.py knows there is a choice.
In the narrow form the workspace path is region-consolidated:
presyn + perm (+ seg_pot) ride ONE one-hot MXU pass per
gather/scatter stage instead of one pass per tensor (bitwise identical per
block — each output element touches only its own operand columns), the
dendrite conn/pot counts share one block-diagonal reduction, and
tick-invariant operands (the reduction matrix) hoist out of the chunk scan
via :func:`tm_invariants`.

Within the narrow form the first stage — the gather of the active columns'
pool rows — has a second exact form, picked the same way in one place
(:func:`gather_by_select`, which says why): a compare-select reduce over the
columns in the pools' own types where a pool row fills whole 128-lane tiles
(384 lanes: the node presets), the one-hot matmul where it does not (192
lanes: the cluster presets).

In either form the compaction of the learning segments within the workspace
engages only where `learn_cap` cuts the workspace's rows
(:func:`compacts_learning_rows`, the third such place): where the cap is the
structural bound col_cap*K*S or above it (the node presets; 32 columns) the
workspace's rows are the learning rows where they lie.

Capacity bounds (col_cap active columns, learn_cap learning segments per
step) are static-shape requirements of XLA; overflow beyond the bounds is
counted in state["tm_overflow"] so tests can assert it never fires at the
configured sizes.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from rtap_tpu.config import TMConfig
from rtap_tpu.models.perm import tm_domain

# numpy scalar, not jnp: a jnp constant at import would initialize the
# backend (and claim the chip) in every process that imports this module
INF = np.float32(np.inf)
_HI = jax.lax.Precision.HIGHEST


# Strategy switch for _compact_ids, whose natural formulation (nonzero)
# serializes on the TPU scalar core: None = per-backend default (top_k
# reformulation on TPU, nonzero elsewhere); tests flip it to cover both code
# paths on the CPU platform. Both paths are bit-identical.
FORCE_TPU_PATHS: bool | None = None


def _tpu_paths() -> bool:
    if FORCE_TPU_PATHS is not None:
        return FORCE_TPU_PATHS
    return jax.default_backend() == "tpu"


#: The one line the shape-chosen forms are drawn at. A pool row is one
#: column's K*S*M synapse lanes. Measured at the two ends on a v5e: at 192
#: lanes (cluster presets, a 384 B row) the flat layout beats aos by 13 % and
#: one-hot matmul moves beat indexed row moves 1.55x (SCALING.md round 4); at
#: 16,384 lanes (nab_preset, a 64 KiB row, G = 17) the matmul moves' f32
#: copies of both pools do not fit the chip (RESOURCE_EXHAUSTED at compile,
#: 15.76 of 15.75 GB; 18.6 GB of arguments and temporaries by the compiler's
#: own account since ISSUE 36), and the indexed moves over [C, M, K*S] pools
#: step a group-tick of 17 streams in 50.53 ms (75.96 on [C, K, S, M] pools,
#: which the chip holds columns-minor and re-laid four times a tick around
#: the moves; chip runs, PRs 40 and 42; PERF.md s6).
#: The line is the geometric middle of the two points, 85x apart, rounded to
#: a power of two; nothing between them has been measured.
WIDE_ROW_LANES = 2048


def _row_lanes(cfg: TMConfig) -> int:
    """Synapse lanes of one pool row: a column's K*S*M slots."""
    return (cfg.cells_per_column * cfg.max_segments_per_cell
            * cfg.max_synapses_per_segment)


def wide_rows(cfg: TMConfig) -> bool:
    """Does this shape take the wide-row form (indexed workspace moves,
    [C, M, K*S] pools in the kernel) rather than the narrow-row one (one-hot
    moves over the whole pool, flat [C, K*S*M] pools)? The one place the
    step's form is decided; both forms are bit-identical to the oracle
    (tests/parity/test_tm_forms.py).

    What the wide layout assumes, for speed and never for correctness: K*S
    (the lanes) a multiple of 128 and M (the sublanes) a multiple of 8, as
    at the NAB width (512 and 32). Another wide shape pads its tiles — the
    sweep and the moves touch the padding too — and computes the same."""
    return _row_lanes(cfg) >= WIDE_ROW_LANES


#: Lanes of one vector register (and of one tile of the chip's memory layouts).
LANE_TILE = 128


def gather_by_select(cfg: TMConfig) -> bool:
    """Within the narrow-row form: are the learning workspace's rows gathered
    by a compare-select reduce in the pools' own types (True) or by the
    one-hot MXU matmul over f32 casts of the pools (False)? The one place it
    is decided, from the static shape: a pool row of whole 128-lane tiles
    takes the select.

    The matmul wants its contracting dimension — the columns — minor in its
    operand, and its operand is the pools as the scan carries them, so it
    holds every [C, K*S*M] leaf of the carry columns-minor. Where a row does
    not fill its last tile (192 lanes: the cluster presets) the whole loop
    body prefers that layout too (lanes-minor would pad 192 to 256), the
    matmul costs no copy, and it is the faster gather: 7.43 against 7.76 ms a
    group-tick at 256 columns, 1.234 against 1.247 at 32 (chip runs, PR 38).
    Where rows are whole tiles (384 lanes: `node_preset`) everything else
    runs lanes-minor and that one operand made the compiler re-lay seven
    pool-sized leaves inside the scan body every tick — the TM's two pools in
    and out, the dense SP's `perm` in and out and its `potential` mask in:
    21.60 against 15.67 ms a group-tick (same runs). Both forms are
    bit-identical to the oracle (tests/parity/test_tm_forms.py)."""
    return not wide_rows(cfg) and _row_lanes(cfg) % LANE_TILE == 0


def compacts_learning_rows(cfg: TMConfig) -> bool:
    """In either form: are the learning segments compacted out of the
    workspace's col_cap*K*S rows into `learn_cap` rows (True), or do reinforce
    and growth run on the workspace's rows where they lie (False)? The one
    place it is decided, from the static shape: the compaction engages only
    where it cuts rows.

    Reinforce and `_grow_compact` treat each row by itself, so the compaction
    is a permutation that is undone afterwards; what it buys is growth's
    grids on `learn_cap` rows instead of all of them (`cluster_preset`: 64 of
    160; `nab_preset`: 1,280 of 20,480). Where the cap is the structural
    bound or above it (`node_preset`: 320 = 320, so that `tm_overflow` is 0
    by construction; `scaled_cluster_preset(32)`: 64 > 48) it buys nothing
    and costs the `top_k` over the learning flags, the [L, R2] one-hot grid,
    its two MXU passes and three reduces over it: 15.06 against 12.56 ms a
    group-tick at the node model's shape, 1.240 against 0.927 at 32 columns
    (chip runs, PR 47; docs/KERNELS.md, "The learn-cap compaction: only
    where it cuts"). Both are bit-identical to the oracle
    (tests/parity/test_tm_forms.py)."""
    return cfg.learn_cap < (cfg.col_cap * cfg.cells_per_column
                            * cfg.max_segments_per_cell)


# TM state keys that change shape in the kernel: key -> how many trailing dims
# the public layout spends on them (pools: K,S,M; segment tensors: K,S).
_KERNEL_KEYS = {
    "presyn": 3, "syn_perm": 3,
    "seg_last": 2, "active_seg": 2, "matching_seg": 2, "seg_pot": 2,
}


def kernel_resident(state: dict) -> bool:
    """Does this tree hold the six `_KERNEL_KEYS` leaves in the kernel's form
    already (the form a stream group keeps on the device between programs)?
    Read off the leaves' shapes alone, with any leading axes: a segment
    tensor is [C, K*S] there — the rank of `prev_active` [C, K] — and
    [C, K, S], one more, in the public layout."""
    return np.ndim(state["seg_last"]) == np.ndim(state["prev_active"])


def resident_leaf(key: str, x, cfg: TMConfig):
    """One `_KERNEL_KEYS` leaf, public layout -> the kernel's form; leading
    axes pass through, the values are untouched. The leaf may be numpy's (a
    host-side view: what a checkpoint read or a fresh stream's row goes
    through before it reaches the device), the device's, or traced."""
    nd = _KERNEL_KEYS[key]
    lead = x.shape[: x.ndim - nd]
    if nd == 3 and wide_rows(cfg):
        return x.reshape(*lead, -1, cfg.max_synapses_per_segment).swapaxes(-1, -2)
    return x.reshape(*lead, -1)


def public_leaf(key: str, x, cfg: TMConfig):
    """`resident_leaf`'s inverse."""
    K, S, M = cfg.cells_per_column, cfg.max_segments_per_cell, cfg.max_synapses_per_segment
    if _KERNEL_KEYS[key] == 2:
        return x.reshape(*x.shape[:-1], K, S)
    if wide_rows(cfg):
        return x.swapaxes(-1, -2).reshape(*x.shape[:-2], K, S, M)
    return x.reshape(*x.shape[:-1], K, S, M)


def resident_form(state: dict, cfg: TMConfig) -> dict:
    """Public state layout -> the form the device holds between programs and
    `tm_step(cfg)` runs on, a function of `cfg` alone. At narrow rows a
    reshape: [C, K*S*M] pools, [C, K*S] segment tensors. At wide rows
    (`wide_rows`) the same segment tensors and the pools with their synapse
    axis turned inward, [C, M, K*S]."""
    return {**state, **{k: resident_leaf(k, state[k], cfg) for k in _KERNEL_KEYS}}


def public_form(state: dict, cfg: TMConfig) -> dict:
    """`resident_form`'s inverse: the public [C, K, S, M] pools and
    [C, K, S] segment tensors — what checkpoints, the oracle and the parity
    harness keep."""
    return {**state, **{k: public_leaf(k, state[k], cfg) for k in _KERNEL_KEYS}}


@lru_cache(maxsize=None)
def _reduce_matrix(ks: int, m: int):
    """Block-diagonal 0/1 [ks*m, ks] f32: column s sums synapse lanes
    [s*m, (s+1)*m) — the per-segment Σ_M reduction as one MXU matmul (the
    flat layout's seg_sum operand)."""
    r = np.zeros((ks * m, ks), np.float32)
    for s in range(ks):
        r[s * m : (s + 1) * m, s] = 1.0
    return r


def tm_invariants(cfg: TMConfig) -> dict | None:  # rtap: allow[twin-parity] — trace-time constant builder (reduction matrix), not a semantic kernel; exercised through every tm_step parity run
    """Tick-invariant device operands of :func:`tm_step`, built ONCE so a
    caller scanning over ticks (ops/step.py:_scan_chunk) can hoist them
    out of the scan body explicitly — they stay HBM-resident across the
    whole T-tick chunk instead of rematerializing as per-iteration
    constants. None at wide rows ([C, M, K*S] pools sum their M axis
    directly)."""
    if wide_rows(cfg):
        return None
    K, S, M = cfg.cells_per_column, cfg.max_segments_per_cell, cfg.max_synapses_per_segment
    return {"red": jnp.asarray(_reduce_matrix(K * S, M))}


def _compact_ids(mask: jnp.ndarray, size: int) -> jnp.ndarray:
    """Indices of the first `size` True entries of `mask` [n], ascending,
    filled with n -> i32 [size].

    Equivalent to jnp.nonzero(mask, size=size, fill_value=n)[0], but on TPU
    nonzero's cumsum+pack runs on the scalar core (profiled in round 1);
    top_k of (n - index) is the vector-unit formulation: descending top_k of
    distinct values = ascending indices.
    """
    n = mask.shape[0]
    if not _tpu_paths():
        return jnp.nonzero(mask, size=size, fill_value=n)[0].astype(jnp.int32)
    k = min(size, n)  # top_k rejects k > n; a cap larger than the domain
    iota = jnp.arange(n, dtype=jnp.int32)
    top = jax.lax.top_k(jnp.where(mask, n - iota, 0), k)[0]
    ids = jnp.where(top > 0, n - top, n).astype(jnp.int32)
    if k < size:
        ids = jnp.concatenate([ids, jnp.full(size - k, n, jnp.int32)])
    return ids


def _pack_active(cells_ck: jnp.ndarray, Ac: int):
    """Column-compact representation of a [C, K] cell set (K <= 32).

    Returns (col_ids [Ac] i32 ascending with C fills, col_masks [Ac] i32
    K-bit packed per-column cell masks, n_cols i32 total occupied columns —
    n_cols > Ac means the compact form is truncated, counted as overflow).
    """
    C, K = cells_ck.shape
    col_any = cells_ck.any(-1)
    col_ids = _compact_ids(col_any, Ac)
    packed = (cells_ck.astype(jnp.int32) << jnp.arange(K, dtype=jnp.int32)).sum(-1)  # [C]
    hit = col_ids[:, None] == jnp.arange(C, dtype=jnp.int32)  # [Ac, C]
    col_masks = jnp.where(hit, packed[None, :], 0).sum(-1)
    return col_ids, col_masks, col_any.sum()


def _presyn_active_packed(
    presyn: jnp.ndarray, col_ids: jnp.ndarray, col_masks: jnp.ndarray, K: int
) -> jnp.ndarray:
    """Is each synapse's presynaptic cell in the packed active set? -> bool,
    presyn's shape. `presyn` [..., M] i16/i32 (-1 = empty, never matches).

    Element-wise from end to end, so XLA fuses it into the caller's one pass
    over the pool: the column's mask is picked by a static chain of selects
    over the Ac entries (ops/sp_tpu.py:_sdr_at_members' form) — `col_ids`
    are distinct and their fills (C) equal no column, so at most one entry
    matches and the chain's value is the one a sum over a [..., Ac] grid
    would give, without the reduce that would stand between the pool and
    the caller's own reduce. The cell id splits by shift and mask where K
    is a power of two (every preset: 8 or 32): the arithmetic shift takes
    -1 to -1 and the mask to K-1, as floor division and Python's modulo do.
    """
    p = presyn.astype(jnp.int32)
    if K & (K - 1) == 0:
        c_pre, k_pre = p >> (K.bit_length() - 1), p & (K - 1)
    else:
        c_pre, k_pre = p // K, p % K  # -1 -> (-1, K-1), masked by p >= 0
    msk = jnp.int32(0)
    for a in range(col_ids.shape[0]):
        msk = jnp.where(c_pre == col_ids[a], col_masks[a], msk)
    return (p >= 0) & (((msk >> k_pre) & 1) > 0)


def _winner_id_list(winner_ck: jnp.ndarray, Ac: int) -> jnp.ndarray:
    """Flat cell-id list of winner cells, ascending where valid, invalid
    entries = N -> i32 [Ac*K]. Winner cells live in <= Ac columns (they are a
    subset of that step's active columns), so a column-compact construction
    avoids a [N]-wide top_k."""
    C, K = winner_ck.shape
    N = C * K
    col_ids = _compact_ids(winner_ck.any(-1), Ac)  # [Ac]
    hit = col_ids[:, None] == jnp.arange(C, dtype=jnp.int32)  # [Ac, C]
    rows = (hit[:, :, None] & winner_ck[None, :, :]).any(1)  # [Ac, K]
    ids = col_ids[:, None] * K + jnp.arange(K, dtype=jnp.int32)[None, :]
    return jnp.where(rows & (col_ids[:, None] < C), ids, N).reshape(-1)


def _segment_learning_mask(
    cfg: TMConfig,
    active_cols: jnp.ndarray,  # bool [C]
    active_seg: jnp.ndarray,  # bool [C, K, S] (prev step)
    matching_seg: jnp.ndarray,  # bool [C, K, S] (prev step)
    seg_pot: jnp.ndarray,  # i32 [C, K, S] (prev step)
    seg_last: jnp.ndarray,  # i32 [C, K, S]
    have_winners: jnp.ndarray,  # bool scalar (any prev winner cells)
):
    """Categorize columns and pick the per-column learning segments.

    Returns (predicted_cols, learn_mask [C, K, S], alloc = (col [C] with C
    where the column allocates nothing, cell [C], slot [C]) for burst-new
    allocations, winner_cells_extra [C, K] winner contributions from burst
    columns, burst [C]). Every per-column choice (best matching segment,
    least-used cell) enters its mask as a compare against an iota; nothing
    is written by index.
    """
    C, K, S = active_seg.shape
    prev_predictive = active_seg.any(-1)  # [C, K]
    predicted_cols = prev_predictive.any(-1)  # [C]

    burst = active_cols & ~predicted_cols
    col_matching = matching_seg.any((-2, -1))  # [C]
    burst_match = burst & col_matching
    burst_new = burst & ~col_matching & have_winners

    # (a) predicted columns: every active segment of every predicted cell learns
    mask_pred = active_cols[:, None, None] & active_seg

    # (b) burst-matching: best matching segment (max seg_pot, lowest flat
    # index). One bit a column, placed by comparing the flat (k, s) iota with
    # best_flat — a column that does not burst-match sets none (its argmax
    # over all -1 is 0, which the burst_match factor masks).
    pot = jnp.where(matching_seg, seg_pot, -1).reshape(C, K * S)
    best_flat = jnp.argmax(pot, axis=-1)  # first max — same as np.argmax
    bm_k = best_flat // S
    bm_mask = (
        burst_match[:, None]
        & (jnp.arange(K * S, dtype=best_flat.dtype)[None, :] == best_flat[:, None])
    ).reshape(C, K, S)

    # (c) burst-new: cell with fewest segments; first free slot else LRU slot
    seg_counts = (seg_last >= 0).sum(-1)  # [C, K]
    bn_k = jnp.argmin(seg_counts, axis=-1)  # first min — matches oracle
    # one-hot select of row bn_k (a [C] gather serializes on TPU); exactly one
    # k matches per column, so the sum passes values (incl. -1) through.
    sel_k = jnp.arange(K, dtype=jnp.int32)[None, :] == bn_k[:, None]  # [C, K]
    row_last = jnp.where(sel_k[:, :, None], seg_last, 0).sum(1)  # [C, S]
    any_free = (row_last < 0).any(-1)
    first_free = jnp.argmax(row_last < 0, axis=-1)
    lru = jnp.argmin(row_last, axis=-1)
    bn_s = jnp.where(any_free, first_free, lru)

    # burst-column winner cells, one compare a branch (the branches are
    # disjoint by column: burst_match has a matching segment, the other none)
    kk = jnp.arange(K, dtype=jnp.int32)[None, :]
    winner_extra = (burst_match[:, None] & (kk == bm_k[:, None])) | (
        (burst & ~col_matching)[:, None] & (kk == bn_k[:, None])  # winner even when no alloc
    )

    alloc_col = jnp.where(burst_new, jnp.arange(C), C)  # C == dropped
    return predicted_cols, mask_pred | bm_mask, (alloc_col, bn_k, bn_s), winner_extra, burst


def _grow_compact(
    cfg: TMConfig,
    presyn_l: jnp.ndarray,  # i32 [L, M] (post-reinforce)
    perm_l: jnp.ndarray,  # f32 [L, M] (domain values: perms or quanta)
    n_grow: jnp.ndarray,  # i32 [L]
    winner_ids: jnp.ndarray,  # i32 [W] ascending where valid, fills = N
    n_cells: int,
    initial_perm: jnp.ndarray,  # f32 scalar, domain value of initial_permanence
):
    """Oracle _grow_synapses, vectorized: per segment, add the first
    min(n_grow, #eligible) winner cells (ascending id, not already
    presynaptic), evicting weakest synapses when free slots run short.

    `n_grow` is at most cfg.new_synapse_count (the caller's
    new_synapse_count - seg_pot).

    No gather and no sort: the r-th chosen winner goes to the r-th free
    slot, so the two ranks are matched on compare grids that fuse into
    their reduces — first `[L, R, W]` (which winner has rank r among the
    chosen), then `[L, M, R]` (which rank a free slot has), R =
    min(new_synapse_count, W). An element-wise XLA gather costs ~10 ns an
    element on a v5e, which made the two lookups this replaces 54-82 % of a
    cluster tick; matching slots against winners in one `[L, M, W]` grid
    ties at 256 columns and at the NAB width and is 1.7 % of a tick slower
    at 32 (PERF.md §6, PR 28)."""
    M = presyn_l.shape[1]
    W = winner_ids.shape[0]

    valid_w = winner_ids < n_cells
    # membership: winner already presynaptic on this segment?  [L, W]
    already = (presyn_l[:, None, :] == winner_ids[None, :, None]).any(-1)
    eligible = valid_w[None, :] & ~already
    rank = jnp.cumsum(eligible, axis=1)  # 1-based among eligible
    chosen = eligible & (rank <= n_grow[:, None])
    n_new = chosen.sum(-1).astype(jnp.int32)  # [L]

    # evict weakest occupied synapses if short of free slots (stable by slot)
    occupied = presyn_l >= 0
    n_free = M - occupied.sum(-1)
    short = n_new - n_free  # [L]
    key = jnp.where(occupied, perm_l, INF)
    # stable ascending rank by (key, slot) via compare-count: M is tiny
    # (<= 32), so the [L, M, M] compare grid is cheap, branch-free VPU
    # work — vs two serialized stable sorts
    kj, ki = key[:, :, None], key[:, None, :]  # [L, M(j), M(i)]
    jj = jnp.arange(M, dtype=jnp.int32)
    before = (kj < ki) | ((kj == ki) & (jj[None, :, None] < jj[None, None, :]))
    ranks = before.sum(1).astype(jnp.int32)  # [L, M]
    evict = occupied & (ranks < short[:, None])
    presyn_l = jnp.where(evict, -1, presyn_l)
    perm_l = jnp.where(evict, 0.0, perm_l)

    # fill free slots ascending with chosen winners ascending: the chosen
    # ranks are 1..n_new, each once, so exactly one winner matches a rank
    # r < n_new and the sum passes its id through (-2 matches no rank); a
    # slot under `assign` has frank < n_new and takes that rank's id
    free = presyn_l < 0
    frank = jnp.cumsum(free, axis=-1) - 1  # 0-based among free slots
    assign = free & (frank < n_new[:, None])
    r = jnp.arange(min(cfg.new_synapse_count, W), dtype=jnp.int32)
    rank_of = jnp.where(chosen, rank - 1, -2)  # [L, W]
    new_ids = jnp.where(
        rank_of[:, None, :] == r[None, :, None], winner_ids[None, None, :], 0
    ).sum(-1)  # [L, R] ascending ids of the chosen
    fill = jnp.where(
        frank[:, :, None] == r[None, None, :], new_ids[:, None, :], 0
    ).sum(-1)  # [L, M]
    presyn_l = jnp.where(assign, fill, presyn_l)
    perm_l = jnp.where(assign, initial_perm, perm_l)
    return presyn_l, perm_l


def _gather_rows_f32(x: jnp.ndarray, oh: jnp.ndarray) -> jnp.ndarray:
    """One-hot row gather as an MXU matmul: oh [R, C] f32 0/1 one-hot rows,
    x [C, F] f32 -> [R, F]. At most one 1.0 per output row, so values pass
    through exactly under HIGHEST precision (full-f32 passes)."""
    return jax.lax.dot(oh, x, precision=_HI)


def _gather_rows_i32(x: jnp.ndarray, oh_b: jnp.ndarray) -> jnp.ndarray:
    """One-hot row gather for i32 values of unbounded magnitude (e.g.
    iteration stamps > 2^24, where the f32 matmul would round): masked
    select + integer sum over the one-hot axis."""
    # oh_b [R, C] bool, x [C, F] i32 -> [R, F]
    return jnp.where(oh_b[:, :, None], x[None, :, :], 0).sum(1)


def _gather_rows_or(xs: tuple, oh_b: jnp.ndarray) -> tuple:
    """One-hot row gather of equal-shaped tensors in their OWN types, all in
    one pass: oh_b [R, C] bool one-hot rows, each x [C, F] -> each [R, F].

    A select of the hit row against zeros, OR-reduced over C as ONE variadic
    reduce: exact in any type (at most one term of an output element is not
    zero; an f32 permanence rides as its bit pattern), a row of zeros where
    `oh_b`'s row hits nothing (the fills — what the one-hot matmul gives
    there), and one fusion whose [R, C, F] selects never reach HBM. On a v5e
    the OR costs 2/3 of a 16-bit unsigned `max`, and two pools in one reduce
    73 % of two reduces (chip runs, PR 38; docs/KERNELS.md, "The workspace
    gather and the carry's layout")."""
    bits = tuple(jax.lax.bitcast_convert_type(x, jnp.uint32) if x.dtype == jnp.float32 else x
                 for x in xs)
    picked = tuple(jnp.where(oh_b[:, :, None], b[None, :, :], jnp.zeros((), b.dtype))
                   for b in bits)
    out = jax.lax.reduce(
        picked, tuple(jnp.zeros((), b.dtype) for b in bits),
        lambda p, q: tuple(a | b for a, b in zip(p, q)), (1,))
    return tuple(jax.lax.bitcast_convert_type(o, x.dtype) if o.dtype != x.dtype else o
                 for o, x in zip(out, xs))


# rtap: twin[TMOracle] — the oracle TM is stateful (TMOracle.compute)
@partial(jax.jit, static_argnames=("cfg", "learn"))
def tm_step(state: dict, active_cols: jnp.ndarray, cfg: TMConfig, learn: bool = True,
            inv: dict | None = None):
    """One TM step -> (new_state, raw anomaly score f32). Pure.

    `state` uses the models/state.py TM layout plus "tm_overflow" (i32
    overflow counter, device-only observability). `inv` optionally carries
    the tick-invariant operands from :func:`tm_invariants` so a scanning
    caller hoists them out of its loop body; None rebuilds them as
    in-trace constants (single-dispatch callers).
    """
    wide = wide_rows(cfg)
    K, S, M = cfg.cells_per_column, cfg.max_segments_per_cell, cfg.max_synapses_per_segment
    C = state["presyn"].shape[0]
    pool_shape = (C, M, K * S) if wide else (C, K * S * M)
    seg_shape = (C, K * S)
    if state["presyn"].shape != pool_shape or state["seg_last"].shape != seg_shape:
        raise ValueError(
            f"{'wide' if wide else 'narrow'} pool rows: tm_step expects "
            f"kernel-layout state ({'[C, M, K*S]' if wide else '[C, K*S*M]'} "
            "pools, [C, K*S] segment tensors — resident_form of the public "
            "tree; ops/step.py applies it); "
            f"got presyn shape {state['presyn'].shape}, "
            f"seg_last shape {state['seg_last'].shape}"
        )
    N = C * K
    L, Ac = cfg.learn_cap, cfg.col_cap
    if K > 32:
        raise ValueError("cells_per_column > 32 unsupported (packed cell masks)")

    def _red():
        if inv is not None:
            return inv["red"]
        return jnp.asarray(_reduce_matrix(K * S, M))

    def seg_sum(x):
        """Per-segment count over synapse lanes -> i32 [*seg_shape]. Flat
        pools reduce via the block-diagonal 0/1 MXU matmul (counts <= M <<
        2^24: f32-exact) instead of a minor-dim sum the tiler pads; wide
        pools sum their M axis."""
        if wide:
            return x.sum(-2)
        return jnp.round(
            jax.lax.dot(x.astype(jnp.float32), _red(), precision=_HI)
        ).astype(jnp.int32)

    def seg_sum2(a, b):
        """TWO per-segment counts in ONE reduction: the operands stack on
        the row axis so flat pools pay a single [2C, K*S*M] MXU pass
        instead of two (fused-region consolidation; bitwise identical per
        block — each output element touches only its own operand rows)."""
        if wide:
            return a.sum(-2), b.sum(-2)
        both = jnp.round(
            jax.lax.dot(
                jnp.concatenate([a, b], 0).astype(jnp.float32), _red(),
                precision=_HI,
            )
        ).astype(jnp.int32)
        return both[:C], both[C:]

    def seg_expand(x):
        """Broadcast a per-segment value onto its synapse lanes."""
        if wide:
            return x[:, None, :]
        return jnp.repeat(x, M, axis=-1)

    # Permanence-domain constants (models/perm.py). The learning workspace
    # computes on integer-VALUED f32 in quantized domains (quanta <= 65535
    # < 2^24 are exact in f32, and the one-hot MXU gathers are f32 anyway),
    # which agrees bit-for-bit with the oracle's int32 arithmetic.
    dom = tm_domain(cfg)
    p_dt = state["syn_perm"].dtype
    p_one = jnp.float32(dom.one)
    p_inc = jnp.float32(dom.rate(cfg.permanence_increment))
    p_dec = jnp.float32(dom.rate(cfg.permanence_decrement))
    p_init = jnp.float32(dom.rate(cfg.initial_permanence))
    p_connected = dom.threshold(cfg.connected_permanence)
    presyn_dt = state["presyn"].dtype

    presyn = state["presyn"]
    syn_perm = state["syn_perm"]
    seg_last = state["seg_last"]
    it = state["tm_iter"] + 1

    # the scope names are the step's vocabulary (ops/step.py SCOPES)
    with jax.named_scope("rtap.tm.activate"):
        # [C, K, S] views of the SMALL segment tensors for the categorization
        # logic (32 KB each — cheap to repack; the MB-scale pools never leave
        # the kernel's layout)
        active_seg4 = state["active_seg"].reshape(C, K, S)
        matching_seg4 = state["matching_seg"].reshape(C, K, S)
        seg_pot4 = state["seg_pot"].reshape(C, K, S)
        seg_last4 = seg_last.reshape(C, K, S)

        prev_predictive = active_seg4.any(-1)  # [C, K]
        prev_pred_cols = prev_predictive.any(-1)
        n_active = active_cols.sum()
        raw = jnp.where(
            n_active > 0,
            1.0 - (active_cols & prev_pred_cols).sum() / jnp.maximum(n_active, 1).astype(jnp.float32),
            0.0,
        )

        have_winners = state["prev_winner"].any()

        predicted_cols, learn_mask, alloc, winner_extra, burst = _segment_learning_mask(
            cfg, active_cols, active_seg4, matching_seg4, seg_pot4,
            seg_last4, have_winners,
        )

        # cell activation / winner selection (pure function of prev state)
        active_cells = (
            jnp.where((active_cols & predicted_cols)[:, None], prev_predictive, False)
            | burst[:, None]
        )
        winner_cells = (
            jnp.where((active_cols & predicted_cols)[:, None], prev_predictive, False)
            | winner_extra
        )

    overflow_learn = jnp.bool_(False)
    with jax.named_scope("rtap.tm.learn"):
        if learn:
            alloc_col, bn_k, bn_s = alloc
            burst_new = alloc_col < C  # [C]

            # --- gather the active columns into the [Ac, ...] workspace ---
            col_ids = _compact_ids(active_cols, Ac)  # [Ac], fills = C
            col_oh_b = col_ids[:, None] == jnp.arange(C, dtype=jnp.int32)  # [Ac, C]
            col_oh = col_oh_b.astype(jnp.float32)
            hit_cols = col_oh_b.any(0)  # [C] columns actually captured (== active_cols sans overflow)

            if wide:
                # move only the <= Ac touched rows; fill slots (id C) clamp to a
                # junk copy of row C-1 that is masked out of learning (ws_learn /
                # ws_alloc are False there) and dropped at scatter-back. A
                # [C, M, K*S] pool is indexed on its leading axis as it stands
                # — a row is one contiguous [M, K*S] block — and what turns to
                # the [K*S, M] order the compaction wants is the workspace,
                # not the pool
                idx_c = jnp.clip(col_ids, 0, C - 1)

                def take_rows(pool, dt):
                    """[Ac, K*S*M] rows of a pool, a row in the public order."""
                    return pool[idx_c].astype(dt).swapaxes(-1, -2).reshape(Ac, -1)

                with jax.named_scope("rtap.tm.learn.rows"):
                    ws_presyn = take_rows(presyn, jnp.int32)
                    ws_perm = take_rows(syn_perm, jnp.float32)
                    ws_last = seg_last.reshape(C, -1)[idx_c].reshape(Ac, K, S)
                    ws_pot = state["seg_pot"].reshape(C, -1)[idx_c].astype(jnp.int32).reshape(Ac, K, S)
                    ws_learn = (
                        learn_mask.reshape(C, -1)[idx_c] & (col_ids < C)[:, None]
                    ).reshape(Ac, K, S)
            else:
                if gather_by_select(cfg):
                    # the rows picked by compare-select in the pools' own
                    # types, both pools in one OR-reduce over C: no operand
                    # wants the columns minor, so the scan's carry keeps every
                    # [C, K*S*M] leaf lanes-minor like the rest of the loop
                    # body (docs/KERNELS.md, "The workspace gather and the
                    # carry's layout"); the casts are of [Ac, K*S*M], not of
                    # the pools
                    ws_presyn, ws_perm = _gather_rows_or((presyn, syn_perm), col_oh_b)
                    ws_presyn = ws_presyn.astype(jnp.int32)
                    ws_perm = ws_perm.astype(jnp.float32)
                    ws_pot = _gather_rows_i32(
                        state["seg_pot"].reshape(C, -1).astype(jnp.int32), col_oh_b)
                else:
                    # ONE one-hot MXU pass gathers presyn + perm + seg_pot
                    # together (fused-region consolidation: each output element
                    # of the concatenated matmul touches only its own operand
                    # block, so the values are bitwise those of the three
                    # separate gathers; seg_pot <= M << 2^24 and cell ids <
                    # 2^24 are f32-exact)
                    KSM = K * S * M
                    cat = jnp.concatenate(
                        [
                            presyn.reshape(C, -1).astype(jnp.float32),
                            syn_perm.reshape(C, -1).astype(jnp.float32),
                            state["seg_pot"].reshape(C, -1).astype(jnp.float32),
                        ],
                        axis=1,
                    )  # [C, 2*KSM + K*S]
                    g = _gather_rows_f32(cat, col_oh)  # [Ac, 2*KSM + K*S]
                    ws_presyn = jnp.round(g[:, :KSM]).astype(jnp.int32)  # [Ac, K*S*M]
                    ws_perm = g[:, KSM:2 * KSM]  # [Ac, K*S*M]
                    ws_pot = jnp.round(g[:, 2 * KSM:]).astype(jnp.int32)
                ws_pot = ws_pot.reshape(Ac, K, S)
                # seg_last carries unbounded iteration stamps (> 2^24 possible):
                # it keeps the exact integer gather
                ws_last = _gather_rows_i32(seg_last.reshape(C, -1), col_oh_b).reshape(Ac, K, S)
                ws_learn = (
                    (col_oh_b[:, :, None] & learn_mask.reshape(C, -1)[None]).any(1).reshape(Ac, K, S)
                )

            # --- burst-new allocation inside the workspace: clear slot + stamp ---
            ws_bn = (col_oh_b & burst_new[None, :]).any(-1)  # [Ac]
            ws_bnk = jnp.where(col_oh_b, bn_k[None, :], 0).sum(-1)  # [Ac]
            ws_bns = jnp.where(col_oh_b, bn_s[None, :], 0).sum(-1)
            sel_k = jnp.arange(K, dtype=jnp.int32)[None, :] == ws_bnk[:, None]  # [Ac, K]
            sel_s = jnp.arange(S, dtype=jnp.int32)[None, :] == ws_bns[:, None]  # [Ac, S]
            ws_alloc = ws_bn[:, None, None] & sel_k[:, :, None] & sel_s[:, None, :]  # [Ac, K, S]
            alloc_lanes = jnp.repeat(ws_alloc.reshape(Ac, K * S), M, axis=-1)  # [Ac, K*S*M]
            ws_presyn = jnp.where(alloc_lanes, -1, ws_presyn)
            ws_perm = jnp.where(alloc_lanes, 0.0, ws_perm)
            ws_pot = jnp.where(ws_alloc, 0, ws_pot)
            ws_last = jnp.where(ws_alloc, it, ws_last)
            ws_learn = ws_learn | ws_alloc

            # --- compact the <= learn_cap learning segments within the
            # workspace, where the cap cuts rows (compacts_learning_rows);
            # where it does not, the workspace's rows are the learning rows
            # in place and the rows that do not learn are masked out after ---
            R2 = Ac * K * S
            compact = compacts_learning_rows(cfg)
            if compact:
                idx = _compact_ids(ws_learn.reshape(-1), L)  # [L], fills = R2
                valid_l = idx < R2
            else:
                valid_l = ws_learn.reshape(-1)  # [R2]
            ws_presyn_r = ws_presyn.reshape(R2, M)
            ws_perm_r = ws_perm.reshape(R2, M)
            if not compact:
                presyn_l, perm_l = ws_presyn_r, ws_perm_r  # [R2, M]
                pot_l = jnp.where(valid_l, ws_pot.reshape(-1), 0)  # [R2]
            elif wide:
                idx_r = jnp.clip(idx, 0, R2 - 1)
                presyn_l = ws_presyn_r[idx_r]  # [L, M]; fill rows junk, see below
                perm_l = ws_perm_r[idx_r]
                pot_l = jnp.where(valid_l, ws_pot.reshape(-1)[idx_r], 0)  # [L]
            else:
                row_oh_b = idx[:, None] == jnp.arange(R2, dtype=jnp.int32)  # [L, R2]
                row_oh = row_oh_b.astype(jnp.float32)
                # presyn + perm compact in ONE [L, R2] MXU pass — same
                # consolidation as the column gather
                gl = _gather_rows_f32(
                    jnp.concatenate([ws_presyn_r.astype(jnp.float32), ws_perm_r], axis=1),
                    row_oh,
                )  # [L, 2M]
                presyn_l = jnp.round(gl[:, :M]).astype(jnp.int32)  # [L, M]
                perm_l = gl[:, M:2 * M]  # [L, M]
                pot_l = jnp.where(row_oh_b, ws_pot.reshape(-1)[None, :], 0).sum(-1)  # [L]

            # prev-step active cells, column-compact (shared by reinforce + punish)
            pcol_ids, pcol_masks, p_cols = _pack_active(state["prev_active"], Ac)

            # reinforce: +inc on synapses to prev-active cells, -dec on the rest
            exists = presyn_l >= 0
            act = _presyn_active_packed(presyn_l, pcol_ids, pcol_masks, K)
            perm_l = jnp.clip(
                perm_l + p_inc * act - p_dec * (exists & ~act),
                0.0,
                p_one,
            )

            # grow toward previous winner cells (ascending id)
            winner_ids = _winner_id_list(state["prev_winner"], Ac)  # [Ac*K]
            n_grow = (cfg.new_synapse_count - pot_l).astype(jnp.int32)
            grown_presyn, grown_perm = _grow_compact(
                cfg, presyn_l, perm_l, n_grow, winner_ids, N, p_init
            )
            grow_ok = have_winners & valid_l
            presyn_l = jnp.where(grow_ok[:, None], grown_presyn, presyn_l)
            perm_l = jnp.where(grow_ok[:, None], grown_perm, perm_l)

            # --- scatter learned rows back into the workspace ---
            if not compact:
                ws_presyn_r = jnp.where(valid_l[:, None], presyn_l, ws_presyn_r)
                ws_perm_r = jnp.where(valid_l[:, None], perm_l, ws_perm_r)
                ws_last = jnp.where(ws_learn, it, ws_last)
            elif wide:
                ws_presyn_r = ws_presyn_r.at[idx].set(presyn_l, mode="drop")
                ws_perm_r = ws_perm_r.at[idx].set(perm_l, mode="drop")
                # the learned rows' stamp is one value, so the rows in idx are
                # named by compare, not by index: idx holds the first L set
                # entries of ws_learn ascending (fills R2), i.e. every set
                # entry up to its largest
                learned = ws_learn & (
                    jnp.arange(R2, dtype=jnp.int32) <= idx.max()
                ).reshape(Ac, K, S)
                ws_last = jnp.where(learned, it, ws_last)
            else:
                last_l = jnp.full((L,), 1, jnp.int32) * it  # [L] seg_last of learned rows
                hit_rows = row_oh_b.any(0)  # [R2]
                # presyn + perm scatter back in ONE transposed one-hot MXU pass
                scat = jax.lax.dot(
                    row_oh.T,
                    jnp.concatenate([presyn_l.astype(jnp.float32), perm_l], axis=1),
                    precision=_HI,
                )  # [R2, 2M]
                scat_presyn = jnp.round(scat[:, :M]).astype(jnp.int32)
                scat_perm = scat[:, M:]
                ws_presyn_r = jnp.where(hit_rows[:, None], scat_presyn, ws_presyn_r)
                ws_perm_r = jnp.where(hit_rows[:, None], scat_perm, ws_perm_r)
                last_scat = jnp.where(row_oh_b, last_l[:, None], 0).sum(0)  # [R2]
                ws_last = jnp.where(
                    hit_rows.reshape(Ac, K, S), last_scat.reshape(Ac, K, S), ws_last
                )

            # --- scatter the workspace back to the pools ---
            if wide:
                # only the <= Ac touched rows are written; fill ids (C) drop
                def put_rows(x, ws):
                    """The workspace's [Ac] rows into `x` where it lies."""
                    if x.ndim == 3:  # a [C, M, K*S] pool
                        flat, rows = x, ws.reshape(Ac, K * S, M).swapaxes(-1, -2)
                    else:
                        flat, rows = x.reshape(C, -1), ws.reshape(Ac, -1)
                    return flat.at[col_ids].set(rows.astype(x.dtype), mode="drop").reshape(x.shape)

                with jax.named_scope("rtap.tm.learn.rows"):
                    presyn = put_rows(presyn, ws_presyn_r)
                    ws_perm_w = ws_perm_r.reshape(Ac, -1)
                    if dom.bits:
                        ws_perm_w = jnp.round(ws_perm_w)  # exact already; belt+braces
                    syn_perm = put_rows(syn_perm, ws_perm_w)
                    seg_last = put_rows(seg_last, ws_last)
            else:
                hit_pool = hit_cols.reshape(C, 1)
                hit_seg = hit_cols.reshape(C, 1)
                # presyn + perm pools restored in ONE [2*KSM, Ac] x [Ac, C] pass,
                # each pool turned to [C, K*S*M] after its slice: the columns
                # stay the product's minor dim, as the scan's carry holds the
                # pools. Formed [C, 2*KSM], its layout ran through the fused
                # sweep that consumes it to the carry and cost four pool
                # copies a tick at 256 columns (ISSUE 36; docs/KERNELS.md)
                KSM = K * S * M
                pools_t = jax.lax.dot(
                    jnp.concatenate(
                        [
                            ws_presyn_r.reshape(Ac, -1).astype(jnp.float32),
                            ws_perm_r.reshape(Ac, -1),
                        ],
                        axis=1,
                    ).T,
                    col_oh,
                    precision=_HI,
                )  # [2*KSM, C]
                pool_presyn = jnp.round(pools_t[:KSM]).astype(presyn_dt).T.reshape(*pool_shape)
                pool_perm_f = pools_t[KSM:]
                if dom.bits:
                    pool_perm_f = jnp.round(pool_perm_f)  # exact already; belt+braces
                pool_perm = pool_perm_f.astype(p_dt).T.reshape(*pool_shape)
                pool_last = jnp.where(
                    col_oh_b[:, :, None], ws_last.reshape(Ac, 1, -1), 0
                ).sum(0).reshape(*seg_shape)
                presyn = jnp.where(hit_pool, pool_presyn, presyn)
                syn_perm = jnp.where(hit_pool, pool_perm, syn_perm)
                seg_last = jnp.where(hit_seg, pool_last, seg_last)

            overflow_learn = (
                (n_active > Ac) | (p_cols > Ac) | (ws_learn.sum() > L)
            )

            # --- punish: matching segments in columns that did not activate,
            # over the full pool ---
            if cfg.predicted_segment_decrement > 0.0:
                pdec = dom.rate(cfg.predicted_segment_decrement)
                acols_seg = active_cols.reshape(C, 1)
                pmask = state["matching_seg"] & ~acols_seg  # [*seg_shape]
                pact = _presyn_active_packed(presyn, pcol_ids, pcol_masks, K)
                sp_c = syn_perm.astype(dom.compute_dtype)
                syn_perm = jnp.where(
                    seg_expand(pmask) & pact,
                    jnp.maximum(sp_c - pdec, dom.zero),
                    sp_c,
                ).astype(p_dt)

            # --- synapse death at permanence <= 0, then empty-segment death ---
            dead = (presyn >= 0) & (syn_perm <= dom.zero)
            presyn = jnp.where(dead, -1, presyn)
            nsyn = seg_sum(presyn >= 0)
            seg_last = jnp.where((seg_last >= 0) & (nsyn == 0), -1, seg_last)

    with jax.named_scope("rtap.tm.dendrite"):
        # --- dendrite activity for t+1 over existing segments ---
        exists_seg = seg_last >= 0
        acol_ids, acol_masks, a_cols = _pack_active(active_cells, Ac)
        # the packed-column truncation applies under inference too — count it always
        tm_overflow = state["tm_overflow"] + (
            overflow_learn | (a_cols > Ac)
        ).astype(jnp.int32)
        syn_act = _presyn_active_packed(presyn, acol_ids, acol_masks, K)
        conn_count, pot_count = seg_sum2(
            syn_act & (syn_perm >= p_connected), syn_act
        )
        active_seg = exists_seg & (conn_count >= cfg.activation_threshold)
        matching_seg = exists_seg & (pot_count >= cfg.min_threshold)
        seg_pot = jnp.where(exists_seg, pot_count, 0).astype(jnp.int16)
        if learn:
            # LRU stamp for active segments (NuPIC stamps under learn only)
            seg_last = jnp.where(active_seg, it, seg_last)

    return {
        **state,
        "presyn": presyn,
        "syn_perm": syn_perm,
        "seg_last": seg_last,
        "active_seg": active_seg,
        "matching_seg": matching_seg,
        "seg_pot": seg_pot,
        "prev_active": active_cells,
        "prev_winner": winner_cells,
        "tm_iter": it.astype(jnp.int32),  # oracle increments under inference too
        "tm_overflow": tm_overflow,
    }, raw
