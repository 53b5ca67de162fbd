"""The device path of the NAB-width family against the benchmark's plain
reference (benchmark/reference, a numpy copy of the oracle that imports
nothing of rtap_tpu), on seeded state, through StreamGroup's chunk path as
the `replay` traffic drives it.

The family: dense SP pool, f32 permanences (perm_bits 0), time-of-day field
on, 32 cells a column. The cases cross every line the shape draws:

- cell ids: C*K <= 32,767 keeps `presyn` i16, beyond it `presyn` is i32
  (models/state.py:presyn_dtype), and with K = 32 the packed per-column cell
  mask uses all 32 bits, sign bit included. Growth takes the lowest winner
  ids first, so on the i32 side the state is seeded with an empty potential
  pool for every column below the i16 range, on both sides alike: every
  winner, and with it every presynaptic id, then lies beyond it;
- pool rows: K*S*M below `tm_tpu.WIDE_ROW_LANES` takes the narrow-row forms
  (flat pools, one-hot matmul moves), at or above it the wide-row ones
  ([C, K, S, M] pools, indexed moves);
- `FORCE_TPU_PATHS` both ways, so the formulations the chip runs are held to
  the same numbers as the ones the CPU backend picks.

Tolerances are the ones benchmark/configs/nab-2048.json states for the chip;
on the CPU backend the readings are 0."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu
from benchmark.feed import make_sine_feed, seed_key
from benchmark.reference.config import ModelConfig as ReferenceConfig
from benchmark.reference.model import ReferenceStream
from rtap_tpu.config import scaled_nab_preset
from rtap_tpu.models.state import presyn_dtype
from rtap_tpu.service.registry import StreamGroup, segment_capacity

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs", "nab-2048.json")) as _f:
    PRECISION = json.load(_f)["precision"]

G, T, CHUNKS, SEED = 2, 8, 3, 27


def family_cfg(columns: int, S: int, M: int):
    base = scaled_nab_preset(columns)
    assert base.tm.cells_per_column == 32 and not base.sp.sparse_pool
    assert base.sp.perm_bits == base.tm.perm_bits == 0
    assert base.date.time_of_day_width > 0
    return dataclasses.replace(base, tm=dataclasses.replace(
        base.tm, max_segments_per_cell=S, max_synapses_per_segment=M))


@pytest.fixture
def tpu_paths(request):
    old = tm_tpu.FORCE_TPU_PATHS
    tm_tpu.FORCE_TPU_PATHS = request.param
    jax.clear_caches()  # the strategy is baked into traced programs
    yield request.param
    tm_tpu.FORCE_TPU_PATHS = old
    jax.clear_caches()


@pytest.mark.parametrize("tpu_paths", [True, False], indirect=True,
                         ids=["tpu_paths", "cpu_paths"])
@pytest.mark.parametrize("columns,ids", [(1023, np.int16), (2048, np.int32)],
                         ids=["i16_ids", "i32_ids"])
@pytest.mark.parametrize("S,M,wide", [(2, 8, False), (4, 16, True)],
                         ids=["narrow_rows", "wide_rows"])
def test_device_path_equals_the_reference(tpu_paths, columns, ids, S, M, wide):
    cfg = family_cfg(columns, S, M)
    assert presyn_dtype(cfg) == ids
    assert tm_tpu.wide_rows(cfg.tm) == wide

    group = StreamGroup(cfg, [f"s{i}" for i in range(G)], seed=SEED,
                        backend="tpu")
    low = (np.iinfo(np.int16).max + 1) // cfg.tm.cells_per_column \
        if ids == np.int32 else 0  # columns whose cells' ids fit i16
    group.state["perm"] = group.state["perm"].at[:, :low].set(0.0)
    group.state["potential"] = group.state["potential"].at[:, :low].set(False)
    values, ts, _ = make_sine_feed(G, T * CHUNKS, seed_key(SEED, 1))
    pending, served = None, []
    for c in range(CHUNKS):  # depth 2 on the one group, as the cell runs it
        h = group.dispatch_chunk(values[c * T:(c + 1) * T],
                                 ts[c * T:(c + 1) * T], learn=True)
        if pending is not None:
            served.append(group.collect_chunk(pending)[0])
        pending = h
    served.append(group.collect_chunk(pending)[0])
    raw = np.concatenate(served)
    assert group.state["presyn"].dtype == ids
    assert int(np.asarray(group.state["tm_overflow"]).sum()) == 0

    ref_cfg = ReferenceConfig.from_dict(cfg.to_dict())
    ref_in_use = []
    for g in range(G):
        ref = ReferenceStream(ref_cfg, SEED)
        ref.state["perm"][:low] = 0.0
        ref.state["potential"][:low] = False
        ref_raw = np.array([ref.run(int(t), float(v))
                            for t, v in zip(ts[:, g], values[:, g])], np.float32)
        assert np.abs(ref_raw - raw[:, g]).max() <= PRECISION["raw_tolerance"]
        for leaf in ("perm", "syn_perm"):
            gap = np.abs(np.asarray(group.state[leaf][g], np.float64)
                         - ref.state[leaf]).max()
            assert gap <= PRECISION["perm_tolerance"], leaf
        # the run really learned: synapses exist, ids beyond i16 where due
        presyn = np.asarray(group.state["presyn"][g])
        assert (presyn >= 0).sum() > 100
        if ids == np.int32:
            assert presyn[presyn >= 0].min() > np.iinfo(np.int16).max
        ref_in_use.append(ref.state["seg_last"] >= 0)
    # the group's segment-pool headroom, counted from the state it holds
    assert group.capacity_stats() == segment_capacity(np.stack(ref_in_use))
