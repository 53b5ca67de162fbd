"""Bytes one kernel of the fused step has to move, from shapes.

Leaf by leaf from the configuration's sizes, like roofline.py's
`state_bytes_per_stream` (the state leaves below sum to it, to the byte):
each kernel — named by its `rtap.*` scope — reads and writes the listed
leaves once per stream-tick and no kernel can take less time than those bytes
at the chip's peak HBM rate. All are memory-bound (integer compares and adds
over the pools, no matrix unit in the floor), so the bytes bound them.

The temporal memory has ONE entry, `rtap.tm`, for all of `rtap.tm.*`
(activate, learn, learn.rows, dendrite): which of those scopes a pool sweep's
time is filed under follows the compiler's choice of fusion root (since PR 36
restore, punish, synapse death and the dendrite membership test are one
fusion under `rtap.tm.dendrite`), so bytes split by scope cannot be kept
right; their sum can. The entry is a floor: what NO implementation of a TM
tick can avoid, each leaf at most once in each direction. Every synapse is
tested against this tick's active cells, so both pools are read; the segment
and cell vectors are rewritten whole every tick. The pools are NOT counted as
written: learning changes at most `learn_cap` segments' rows and the punished
segments' permanences, so a program that writes only those is possible, and a
floor that charged the whole pools' write would read up to 200 % for it. The
per-scope entries this one replaces (`rtap.tm.learn`: pools read and written
whole; `rtap.tm.dendrite`: pools read once more) asked for three reads and
one write of the pools a tick and read 109.06 % for `rtap.tm.learn` at the
NAB width the moment PR 40 took the layout copies out of that scope (ledger,
PR 40: 11.386 ms of floor over 8.419 + 2.022 ms)."""

from __future__ import annotations

from benchmark.roofline import _index_bytes, peaks

#: the model state's leaves (rtap_tpu/models/state.py), as in roofline.py
STATE_LEAVES = (
    "members", "perm", "boost", "overlap_duty", "active_duty", "sp_iter",
    "presyn", "syn_perm", "seg_last", "active_seg", "matching_seg", "seg_pot",
    "prev_active", "prev_winner", "tm_iter", "tm_overflow",
    "enc_offset", "enc_bound", "enc_resolution")

#: scope -> (leaves read, leaves written). `sdr`, `overlap`, `active_cols`
#: and `active_cells` are the vectors the stages hand each other. A scope's
#: sub-scopes are part of it (`rtap.tm` is every `rtap.tm.*`).
KERNELS = {
    "rtap.sp.overlap": (("members", "perm", "sdr"), ("overlap",)),
    "rtap.sp.learn": (
        ("members", "perm", "sdr", "overlap", "active_cols", "overlap_duty",
         "active_duty", "sp_iter"),
        ("perm", "overlap_duty", "active_duty", "sp_iter")),
    "rtap.tm": (
        ("presyn", "syn_perm", "seg_last", "seg_pot", "matching_seg",
         "active_seg", "prev_active", "prev_winner", "active_cols",
         "active_cells"),
        ("active_seg", "matching_seg", "seg_pot", "seg_last", "prev_active",
         "prev_winner")),
}


def leaf_bytes(model: dict) -> dict[str, int]:
    """Bytes per stream of every state leaf and hand-over vector of a
    sparse-pool, single-field HTM model (a configuration's `model` group)."""
    sp, tm, rdse, date = model["sp"], model["tm"], model["rdse"], model["date"]
    if not sp["sparse_pool"] or model["n_fields"] != 1:
        raise ValueError("shape-derived bytes cover the sparse-pool "
                         "single-field family only")
    C = sp["columns"]
    n_in = rdse["size"] + date["time_of_day_size"] + date["weekend_width"]
    P = round(n_in * sp["potential_pct"])
    K, S, M = (tm["cells_per_column"], tm["max_segments_per_cell"],
               tm["max_synapses_per_segment"])
    perm_b = {0: 4, 8: 1, 16: 2}
    return {
        "members": C * P * _index_bytes(n_in),
        "perm": C * P * perm_b[sp["perm_bits"]],
        "boost": C * 4, "overlap_duty": C * 4, "active_duty": C * 4,
        "presyn": C * K * S * M * _index_bytes(C * K),
        "syn_perm": C * K * S * M * perm_b[tm["perm_bits"]],
        "seg_last": C * K * S * 4,
        "active_seg": C * K * S, "matching_seg": C * K * S,
        "seg_pot": C * K * S * 2,
        "prev_active": C * K, "prev_winner": C * K,
        "sp_iter": 4, "tm_iter": 4, "tm_overflow": 4,
        "enc_offset": 4, "enc_bound": 1, "enc_resolution": 4,
        # bool SDR, i32 overlap per column, bool active columns / cells
        "sdr": n_in, "overlap": C * 4, "active_cols": C, "active_cells": C * K,
    }


def kernel_bytes_per_stream(scope: str, model: dict) -> int:
    """Bytes the kernel under `scope` reads plus writes per stream-tick."""
    if scope not in KERNELS:
        raise KeyError(f"no byte count for scope {scope!r} "
                       f"(has {sorted(KERNELS)})")
    leaves = leaf_bytes(model)
    read, written = KERNELS[scope]
    return sum(leaves[k] for k in read) + sum(leaves[k] for k in written)


def kernel_floor_seconds(scope: str, model: dict, group_size: int,
                         device_kind: str) -> float:
    """Least time one tick of one group can spend in the kernel under
    `scope` on `device_kind`: its bytes at the peak HBM rate."""
    return (kernel_bytes_per_stream(scope, model) * group_size
            / peaks(device_kind)["hbm_bytes_per_s"])
