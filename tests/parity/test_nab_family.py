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


# ---- the family's input and winner masks at their edges (ISSUE 31) --------
# SDR bits and SP winners are compares against an iota on the device, where
# they were index writes; the reference still writes by index.

_DAY0 = 19_676 * 86_400  # 2023-11-15 00:00:00 UTC
_RECORDS = {
    "ring_start": (_DAY0, 41.5),           # the time-of-day run reaches back over the ring's end
    "ring_end": (_DAY0 + 86_399, 41.5),    # ... and over into its start
    "ring_middle": (_DAY0 + 43_200, 41.5),
    "missing_value": (_DAY0 + 43_200, float("nan")),  # date bits only
    "infinite_value": (_DAY0 + 5, float("inf")),
    "saturday_noon": (_DAY0 + 3 * 86_400 + 43_200, 0.0),  # no weekend bits in this family
}


@pytest.mark.parametrize("record", sorted(_RECORDS))
def test_family_sdr_and_winners_equal_the_reference(record):
    import jax.numpy as jnp

    from benchmark.reference.encoders import encode_record
    from benchmark.reference.spatial_pooler import sp_compute
    from benchmark.reference.state import init_state as reference_state
    from rtap_tpu.models.state import init_state
    from rtap_tpu.ops.encoders_tpu import encode_device
    from rtap_tpu.ops.sp_tpu import sp_step

    cfg = family_cfg(256, 2, 8)
    assert cfg.date.weekend_width == 0 and cfg.date.time_of_day_width > 0
    ref_cfg = ReferenceConfig.from_dict(cfg.to_dict())
    ts, v = _RECORDS[record]
    off = np.float32([40.0])
    want = encode_record(ref_cfg, np.float64([v]), ts, off)
    got = np.asarray(encode_device(cfg, jnp.float32([v]), jnp.int32(ts), jnp.asarray(off)))
    np.testing.assert_array_equal(got, want)
    R = cfg.field_size
    assert got[R:].sum() == cfg.date.time_of_day_width
    if np.isfinite(v):
        assert 0 < got[:R].sum() <= cfg.rdse.active_bits  # hash bits may collide
    else:
        assert not got[:R].any()  # a missing sample sets no bit
    if record in ("ring_start", "ring_end"):
        assert got[R] and got[-1]

    ref_state = reference_state(ref_cfg, SEED)
    state = {k: jnp.asarray(x) for k, x in init_state(cfg, SEED).items()}
    want_active = sp_compute(ref_state, want, ref_cfg.sp, learn=True)
    state, got_active = sp_step(state, jnp.asarray(got), cfg.sp, learn=True)
    np.testing.assert_array_equal(np.asarray(got_active), want_active)
    assert want_active.sum() <= cfg.sp.num_active_columns
    np.testing.assert_array_equal(np.asarray(state["perm"]), ref_state["perm"])
