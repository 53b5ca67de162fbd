"""Bytes the algorithm needs, from shapes, and the chip's peaks.

The fused step is memory-bound: one tick of one stream has to read its whole
model state once and write it once (every leaf can change under learning), so
the floor of a group's tick is 2 x state bytes / peak HBM bytes per second.
`state_bytes_per_stream` derives the bytes from the configuration's sizes and
nothing else — not from XLA's cost model, not from the program's arrays."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _index_bytes(n: int) -> int:
    """Signed index dtype that holds 0..n-1 and the -1 sentinel."""
    return 2 if n <= (1 << 15) - 1 else 4


def state_bytes_per_stream(model: dict) -> int:
    """Per-stream state bytes of a sparse-pool, single-field HTM model (the
    `model` group of a benchmark configuration file), leaf by leaf."""
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
    leaves = {
        "members": C * P * _index_bytes(n_in),
        "perm": C * P * perm_b[sp["perm_bits"]],
        "boost+overlap_duty+active_duty": 3 * C * 4,
        "presyn": C * K * S * M * _index_bytes(C * K),
        "syn_perm": C * K * S * M * perm_b[tm["perm_bits"]],
        "seg_last": C * K * S * 4,
        "active_seg+matching_seg": 2 * C * K * S,
        "seg_pot": C * K * S * 2,
        "prev_active+prev_winner": 2 * C * K,
        "sp_iter+tm_iter+tm_overflow": 12,
        "enc_offset+enc_bound+enc_resolution": 9,
    }
    return sum(leaves.values())


def step_floor_seconds(model: dict, group_size: int, device_kind: str) -> float:
    """Least time one tick of one group can take on `device_kind`: its state
    read once and written once at the peak HBM rate."""
    return (2 * state_bytes_per_stream(model) * group_size
            / peaks(device_kind)["hbm_bytes_per_s"])


def peaks(device_kind: str) -> dict:
    """The table's row for `device_kind`; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (has {sorted(k for k in table if k[0] != '_')})")
    return table[device_kind]
