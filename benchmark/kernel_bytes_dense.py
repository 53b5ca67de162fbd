"""Bytes the dense-pool family has to move, from shapes.

benchmark/roofline.py and benchmark/kernel_bytes.py cover the sparse-pool
single-field family and raise for anything else; this is the same account for
the family `nab-2048` belongs to: a dense SP pool (bool potential mask +
permanences over every input), F = `n_fields` >= 1 uniform RDSE value fields
fused into one SDR plus the date encoder's bits, permanences in the
configured domain. Leaf by leaf from the configuration's sizes and nothing
else; the state leaves sum to what rtap_tpu/models/state.py:init_state
allocates, to the byte (281,628,693 B a stream at nab_preset, 760,871 at
node_preset(3); tests/benchmark/test_roofline.py).

Each kernel — named by its `rtap.*` scope — reads and writes the listed
leaves once per stream-tick, so no kernel can take less time than those bytes
at the chip's peak HBM rate. All are memory-bound: the dense overlap reads
3.7 MB of permanences for a 2048 x 454 matvec (1.9 MFLOP); the TM is integer
compares and adds over the pools."""

from __future__ import annotations

from benchmark.kernel_bytes import KERNELS as _SPARSE_KERNELS
from benchmark.roofline import _index_bytes, peaks

#: the model state's leaves (rtap_tpu/models/state.py), dense SP layout
STATE_LEAVES = (
    "potential", "perm", "boost", "overlap_duty", "active_duty", "sp_iter",
    "presyn", "syn_perm", "seg_last", "active_seg", "matching_seg", "seg_pot",
    "prev_active", "prev_winner", "tm_iter", "tm_overflow",
    "enc_offset", "enc_bound", "enc_resolution")

#: scope -> (leaves read, leaves written); `sdr`, `overlap`, `active_cols`
#: and `active_cells` are the vectors the stages hand each other. A scope's
#: sub-scopes are part of it (`rtap.tm` is every `rtap.tm.*`). The TM does
#: not know which SP pool feeds it: its one entry, the floor of the whole
#: temporal memory, is kernel_bytes.py's, leaf for leaf (its docstring says
#: why the pools' write is not in it); the SP's read the potential mask where
#: the sparse family reads member indices.
KERNELS = {
    "rtap.sp.overlap": (("potential", "perm", "sdr"), ("overlap",)),
    "rtap.sp.learn": (
        ("potential", "perm", "sdr", "overlap", "active_cols", "overlap_duty",
         "active_duty", "sp_iter"),
        ("perm", "overlap_duty", "active_duty", "sp_iter")),
    "rtap.tm": _SPARSE_KERNELS["rtap.tm"],
}

_PERM_BYTES = {0: 4, 8: 1, 16: 2}


def leaf_bytes(model: dict) -> dict[str, int]:
    """Bytes per stream of every state leaf and hand-over vector of a
    dense-pool HTM model of F uniform RDSE value fields (a configuration's
    `model` group); the date encoder's bits are part of the input. Only the
    encoder's three leaves and what follows the input's width know F: the TM
    does not know how many fields fed the SP."""
    sp, tm, rdse, date = model["sp"], model["tm"], model["rdse"], model["date"]
    F = model["n_fields"]
    if (sp["sparse_pool"] or F < 1
            or model["composite"] is not None or model["scalar"] is not None
            or model["classifier"]["enabled"]):
        raise ValueError(
            "these bytes cover the dense-pool family of n_fields >= 1 uniform "
            "RDSE fields only: not a sparse pool (benchmark/kernel_bytes.py "
            "has that one), not a composite encoder (its delta fields carry "
            "an enc_prev leaf), not a scalar encoder, not an enabled "
            "classifier")
    C = sp["columns"]
    # = ModelConfig.input_size
    n_in = F * rdse["size"] + date["time_of_day_size"] + date["weekend_width"]
    K, S, M = (tm["cells_per_column"], tm["max_segments_per_cell"],
               tm["max_synapses_per_segment"])
    return {
        "potential": C * n_in,
        "perm": C * n_in * _PERM_BYTES[sp["perm_bits"]],
        "boost": C * 4, "overlap_duty": C * 4, "active_duty": C * 4,
        "presyn": C * K * S * M * _index_bytes(C * K),
        "syn_perm": C * K * S * M * _PERM_BYTES[tm["perm_bits"]],
        "seg_last": C * K * S * 4,
        "active_seg": C * K * S, "matching_seg": C * K * S,
        "seg_pot": C * K * S * 2,
        "prev_active": C * K, "prev_winner": C * K,
        "sp_iter": 4, "tm_iter": 4, "tm_overflow": 4,
        "enc_offset": 4 * F, "enc_bound": F, "enc_resolution": 4 * F,
        # bool SDR, i32 overlap per column, bool active columns / cells
        "sdr": n_in, "overlap": C * 4, "active_cols": C, "active_cells": C * K,
    }


def state_bytes_per_stream(model: dict) -> int:
    leaves = leaf_bytes(model)
    return sum(leaves[k] for k in STATE_LEAVES)


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


def step_floor_seconds(model: dict, group_size: int, device_kind: str) -> float:
    """Least time one tick of one group can take: its state read once and
    written once at the peak HBM rate (as benchmark/roofline.py's)."""
    return (2 * state_bytes_per_stream(model) * group_size
            / peaks(device_kind)["hbm_bytes_per_s"])
