"""The multivariate per-node model as a deployment (ISSUE 37): what
`node_preset`'s own `learn_cap` is for, on the CPU at a small group.

A matured model re-enters a familiar sequence through a burst: every cell of
the bursting columns is active, so every segment that any context ever grew
onto them turns active at once, and on the next tick all of them learn —
more than the cluster preset's 64, which the node model used to inherit.
At the structural bound (col_cap x cells x segments = 320) `StreamGroup`'s
chunk path equals the numpy oracle and counts no overflow; at 64 the same
drive is truncated, counted, and the state differs. Beside it: the fused
three-field encoder against the oracle's (a missing field, two fields
swapped). The scopes its program carries: tests/unit/test_step_scopes.py."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import rtap_tpu.models.oracle.temporal_memory as oracle_tm
from rtap_tpu.config import dense_cluster_preset, node_preset
from rtap_tpu.models.htm_model import HTMModel
from rtap_tpu.models.oracle.encoders import encode_record
from rtap_tpu.ops.encoders_tpu import encode_device
from rtap_tpu.service.registry import StreamGroup

T = 8           # ticks a chunk
N_CONTEXTS = 8  # a sequence `x A B` learnt under eight different x
CYCLES = 10     # ... ten times over: 240 ticks of maturing
SPACING = 8.0   # RDSE buckets 16 apart: no two values share an active bit
SEED = 3


def _drive() -> np.ndarray:
    """[ticks, 3]: the maturing cycles, then a novel value in all three
    fields, then A out of context (its columns burst), then B."""
    ctx = [SPACING * i for i in range(N_CONTEXTS)]
    a, b, novel = (SPACING * (N_CONTEXTS + k) for k in (1, 3, 6))
    seq = [v for _ in range(CYCLES) for x in ctx for v in (x, a, b)]
    seq += [novel, a, b, ctx[0]]
    seq += [ctx[0]] * (-len(seq) % T)  # whole chunks
    one = np.asarray(seq, np.float32)
    return np.stack([one, one + 1.0, one + 2.0], axis=1)


def _feed() -> tuple[np.ndarray, np.ndarray]:
    """[ticks, G=2, 3] values and [ticks, G] stamps: stream 0 takes the
    drive, stream 1 a plain ramp (it never bursts past the cap)."""
    drive = _drive()
    n = len(drive)
    ramp = np.stack([20.0 + (np.arange(n) % 5) * SPACING + f
                     for f in range(3)], axis=1).astype(np.float32)
    values = np.stack([drive, ramp], axis=1)
    ts = (1_700_000_000 + np.arange(n)[:, None] + np.zeros((1, 2))).astype(np.int64)
    return values, ts


@pytest.fixture(scope="module")
def oracle_run():
    """The oracle over stream 0's drive -> (raw [ticks], final state,
    segments that learnt in each tick by the oracle's own calls)."""
    counted = [0]

    def counting(inner):
        def call(*a, **k):
            counted[0] += 1
            return inner(*a, **k)
        return call

    with pytest.MonkeyPatch.context() as patch:
        for name in ("_reinforce_and_grow", "_allocate_segment"):
            patch.setattr(oracle_tm, name, counting(getattr(oracle_tm, name)))
        values, ts = _feed()
        model = HTMModel(node_preset(3), seed=SEED, backend="cpu")
        raw, learnt = [], []
        for i in range(len(values)):
            counted[0] = 0
            raw.append(model.run(int(ts[i, 0]), values[i, 0]).raw_score)
            learnt.append(counted[0])
    return np.asarray(raw, np.float32), model.state, np.asarray(learnt)


def _group_run(cfg):
    values, ts = _feed()
    group = StreamGroup(cfg, ["n0", "n1"], seed=SEED, backend="tpu")
    raw = np.concatenate([group.run_chunk(values[i:i + T], ts[i:i + T])[0]
                          for i in range(0, len(values), T)])
    state = {k: np.asarray(group.state[k]) for k in
             ("perm", "syn_perm", "presyn", "tm_overflow")}
    return raw, state


def test_a_burst_past_64_learns_whole_at_the_structural_bound(oracle_run):
    ref_raw, ref_state, learnt = oracle_run
    # the drive does what it is for: one tick in which more segments learn
    # than the inherited cap held, fewer than the bound
    assert 64 < learnt.max() <= 320
    cfg = node_preset(3)
    assert cfg.tm.learn_cap == 320
    raw, state = _group_run(cfg)
    assert state["tm_overflow"].tolist() == [0, 0]
    assert np.abs(raw[:, 0] - ref_raw).max() <= 1e-6
    for leaf in ("perm", "syn_perm", "presyn"):
        assert np.array_equal(state[leaf][0], ref_state[leaf]), leaf


def test_the_same_drive_at_the_inherited_cap_is_truncated_and_differs(oracle_run):
    ref_raw, ref_state, learnt = oracle_run
    burst_ticks = np.nonzero(learnt > 64)[0]
    cfg = node_preset(3)
    cfg = dataclasses.replace(cfg, tm=dataclasses.replace(cfg.tm, learn_cap=64))
    raw, state = _group_run(cfg)
    # one count a tick whose burst was cut, on the driven stream only
    assert state["tm_overflow"].tolist() == [len(burst_ticks), 0]
    assert not np.array_equal(state["syn_perm"][0], ref_state["syn_perm"])
    # ... and up to the first cut the two caps are the same program
    first = int(burst_ticks[0])
    assert np.abs(raw[:first + 1, 0] - ref_raw[:first + 1]).max() <= 1e-6


@pytest.mark.parametrize("n", [1, 3, 5])
def test_node_preset_states_the_structural_bound(n):
    tm = node_preset(n).tm
    assert tm.learn_cap == tm.col_cap * tm.cells_per_column * \
        tm.max_segments_per_cell == 320
    assert node_preset(n).n_fields == n
    # nothing else of the dense geometry moved
    base = dense_cluster_preset()
    assert dataclasses.replace(node_preset(n), n_fields=1, tm=base.tm) == base


def test_the_dense_cluster_preset_keeps_its_cap():
    assert dense_cluster_preset().tm.learn_cap == 64


# ---- the fused encoder ----

def _encode_both(cfg, values, offset):
    values = np.asarray(values, np.float32)
    offset = np.asarray(offset, np.float32)
    res = np.asarray(cfg.field_resolutions(), np.float32)
    host = encode_record(cfg, values, 1_700_000_000, offset, res)
    dev = np.asarray(encode_device(cfg, jnp.asarray(values),
                                   jnp.int32(1_700_000_000),
                                   jnp.asarray(offset), jnp.asarray(res)))
    assert np.array_equal(host, dev)
    return host


@pytest.mark.parametrize("missing", [0, 1, 2])
def test_a_missing_field_leaves_the_other_two_fields_bits_alone(missing):
    cfg = node_preset(3)
    R = cfg.field_size
    values, offset = [41.5, 63.0, 12.25], [40.0, 60.0, 10.0]
    whole = _encode_both(cfg, values, offset)
    assert whole.shape == (3 * R,) and whole.sum() <= 3 * cfg.rdse.active_bits
    values[missing] = np.nan
    holed = _encode_both(cfg, values, offset)  # the device's equals the oracle's
    for f in range(3):
        bits = holed[f * R:(f + 1) * R]
        if f == missing:
            assert not bits.any()
        else:
            assert bits.any() and np.array_equal(bits, whole[f * R:(f + 1) * R])


def test_two_fields_swapped_is_another_sdr():
    cfg = node_preset(3)
    offset = [40.0, 40.0, 40.0]
    a = _encode_both(cfg, [41.5, 63.0, 12.25], offset)
    b = _encode_both(cfg, [63.0, 41.5, 12.25], offset)
    R = cfg.field_size
    assert not np.array_equal(a[:2 * R], b[:2 * R])
    assert np.array_equal(a[2 * R:], b[2 * R:])
    # the same value in another field lights other bits: a field's hash is its own
    assert not np.array_equal(a[:R], b[R:2 * R])

