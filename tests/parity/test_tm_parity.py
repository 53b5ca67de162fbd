"""Oracle-vs-device TM parity (SURVEY.md §4 item 2) — the crown-jewel test.

Runs the numpy TM oracle and the jitted device kernel from identical initial
state over identical active-column sequences and asserts bit-identical pools
(presyn, syn_perm, seg_last), cell states, and raw anomaly scores each step.
Sequences mix repetition (segment reinforcement), novelty (bursting, segment
allocation), ambiguity (shared prefixes -> multiple predicted cells), and
resets, to reach every learning branch including LRU eviction and
weakest-synapse eviction.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rtap_tpu.config import TMConfig
from rtap_tpu.models.oracle.temporal_memory import TMOracle
from rtap_tpu.models.perm import tm_domain
from rtap_tpu.ops import tm_tpu
from rtap_tpu.ops.tm_tpu import public_form, resident_form, tm_step

TM_KEYS = (
    "presyn", "syn_perm", "seg_last", "active_seg", "matching_seg",
    "seg_pot", "prev_active", "prev_winner", "tm_iter", "tm_overflow",
)


def _init_tm_state(C, cfg: TMConfig):
    K, S, M = cfg.cells_per_column, cfg.max_segments_per_cell, cfg.max_synapses_per_segment
    return {
        "presyn": np.full((C, K, S, M), -1, np.int32),
        "syn_perm": np.zeros((C, K, S, M), np.float32),
        "seg_last": np.full((C, K, S), -1, np.int32),
        "active_seg": np.zeros((C, K, S), bool),
        "matching_seg": np.zeros((C, K, S), bool),
        "seg_pot": np.zeros((C, K, S), np.int32),
        "prev_active": np.zeros((C, K), bool),
        "prev_winner": np.zeros((C, K), bool),
        "tm_iter": np.int32(0),
        "tm_overflow": np.int32(0),
    }


def _assert_state_equal(host, dev, step):
    for key in TM_KEYS:
        if key == "tm_overflow":
            assert int(dev[key]) == 0, f"device capacity overflow at step {step}"
            continue
        np.testing.assert_array_equal(
            np.asarray(host[key]), np.asarray(dev[key]), err_msg=f"{key} step {step}"
        )


@pytest.fixture(params=["narrow", "wide"])
def rows(request, monkeypatch):
    """Every scenario in both forms of the step at ITS OWN shape (the pools
    must fill for the eviction branches, which a shape wide by itself never
    does here): the line between the forms is moved under the shape, and the
    caches cleared because the form is read at trace time."""
    monkeypatch.setattr(tm_tpu, "WIDE_ROW_LANES",
                        1 << 30 if request.param == "narrow" else 1)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


def _run_parity(C, cfg, sequences, learn=True, host=None):
    host = _init_tm_state(C, cfg) if host is None else host
    # the public [C, K, S, M] layout crosses the boundary via the same
    # adapters ops/step.py uses, once each way; between the steps the state
    # is resident, as an owner of a state holds it
    dev = resident_form(
        {k: jnp.asarray(v) for k, v in copy.deepcopy(host).items()}, cfg)
    oracle = TMOracle(host, cfg)
    for step, cols in enumerate(sequences):
        active = np.zeros(C, bool)
        active[cols] = True
        raw_host = oracle.compute(active, learn=learn)
        dev, raw_dev = tm_step(dev, jnp.asarray(active), cfg, learn=learn)
        assert abs(raw_host - float(raw_dev)) < 1e-6, f"raw score step {step}"
        _assert_state_equal(host, public_form(dev, cfg), step)


def _pattern(rng, C, n_active):
    return rng.choice(C, size=n_active, replace=False)


@pytest.mark.quick
@pytest.mark.parametrize("learn", [True, False])
def test_tm_parity_repeating_sequence(learn, rows):
    """A-B-C-D repeated: drives prediction, reinforcement, growth."""
    C, cfg = 64, TMConfig(
        cells_per_column=8, activation_threshold=3, min_threshold=2,
        max_segments_per_cell=4, max_synapses_per_segment=12,
        new_synapse_count=6, learn_cap=32,
    )
    rng = np.random.default_rng(11)
    pats = [_pattern(rng, C, 5) for _ in range(4)]
    seq = pats * 10
    _run_parity(C, cfg, seq, learn=learn)


def test_tm_parity_ambiguous_sequences(rows):
    """A-B-C-D vs A-B-C-E (shared prefix) -> multiple predicted cells per
    column, multi-segment learning in predicted columns."""
    C, cfg = 64, TMConfig(
        cells_per_column=8, activation_threshold=3, min_threshold=2,
        max_segments_per_cell=4, max_synapses_per_segment=12,
        new_synapse_count=6, learn_cap=32,
    )
    rng = np.random.default_rng(5)
    A, B, Cp, D, E = (_pattern(rng, C, 5) for _ in range(5))
    seq = ([A, B, Cp, D] * 5 + [A, B, Cp, E] * 5) * 3
    _run_parity(C, cfg, seq)


def test_tm_parity_random_stream_with_eviction(rows):
    """Random novelty: constant bursting + allocation until pools fill and
    LRU segment eviction + weakest-synapse eviction kick in."""
    C, cfg = 32, TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=6,
        new_synapse_count=4, learn_cap=32,
    )
    rng = np.random.default_rng(23)
    seq = [_pattern(rng, C, 4) for _ in range(120)]
    _run_parity(C, cfg, seq)


@pytest.mark.parametrize("slots", ["every_slot_a_column", "last_column_beside_fills",
                                   "some_ticks_all_fills"])
def test_tm_parity_workspace_slots_at_their_edges(rows, compact_paths, slots):
    """The learning workspace's `col_cap` slots, in both forms and under both
    `_compact_ids`: a burst that fills every slot (no fill id anywhere); fewer
    active columns than slots with column C-1 among them — at wide rows a fill
    slot (id C) is gathered from a clamped index, a junk copy of row C-1, and
    the scatter back must drop it (`mode="drop"`), not lay it over the real
    row C-1's learning; and ticks with no active column at all, every slot a
    fill."""
    C, cap = 32, 6
    cfg = TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=6,
        new_synapse_count=4, learn_cap=cap * 4 * 2, col_cap=cap,
    )
    rng = np.random.default_rng(47)
    if slots == "every_slot_a_column":
        pats = [np.append(_pattern(rng, C - 1, cap - 1), C - 1) if i % 2
                else _pattern(rng, C, cap) for i in range(5)]
        seq = pats * 8 + [_pattern(rng, C, cap) for _ in range(40)]
    else:
        pats = [np.append(_pattern(rng, C - 1, 3), C - 1) for _ in range(4)]
        seq = pats * 8 + [np.append(_pattern(rng, C - 1, 2), C - 1) for _ in range(40)]
        if slots == "some_ticks_all_fills":
            seq = [p if i % 5 else np.array([], int) for i, p in enumerate(seq)]
    _run_parity(C, cfg, seq)


@pytest.mark.quick
@pytest.mark.parametrize("S,M,wide,select", [
    (2, 6, False, False), (16, 32, True, False),
    (4, 8, False, True), (4, 12, False, False), (8, 12, False, True),
], ids=["narrow", "wide", "lanes128", "lanes192", "lanes384"])
def test_tm_parity_explicit_layouts(S, M, wide, select):
    """Full state parity in BOTH forms where the shape itself picks the form
    (the other tests move the line under one shape): 48 lanes a row, and
    2,048 — and, within the narrow form, under both workspace gathers where
    the shape picks the gather: the compare-select reduce at rows of whole
    128-lane tiles (128, 384), the one-hot matmul at 192 (and at 48)."""
    C, cfg = 32, TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=S, max_synapses_per_segment=M,
        new_synapse_count=4, learn_cap=32,
    )
    assert tm_tpu.wide_rows(cfg) == wide
    assert tm_tpu.gather_by_select(cfg) == select
    rng = np.random.default_rng(29)
    seq = [_pattern(rng, C, 4) for _ in range(60)]
    _run_parity(C, cfg, seq)


@pytest.mark.parametrize("perm", ["u16", "u8", "f32"])
@pytest.mark.parametrize("ids", ["i16", "i32"])
@pytest.mark.parametrize("hits", ["sparse", "full_cap", "all_fills"])
def test_gather_rows_or_picks_the_rows_exactly(hits, ids, perm):
    """The gather alone: `_gather_rows_or` against `pool[col_ids]`, a row of
    zeros where `col_ids` holds its fill C (the row the one-hot matmul gave
    there), the pools in their own types and together in one call — cell ids
    up to the type's largest beside the -1 empties, permanences over the
    whole range of their domain (quanta >= 2^15 set a 16-bit sign bit; f32
    rides as its bit pattern, -0.0 kept) — and equal to the f32 one-hot
    matmul's rows wherever that is exact."""
    C, F, Ac = 24, 40, 6
    rng = np.random.default_rng(43)
    id_dt = {"i16": np.int16, "i32": np.int32}[ids]
    top = np.iinfo(id_dt).max
    presyn = rng.integers(-1, 200, (C, F)).astype(id_dt)
    presyn[rng.random((C, F)) < 0.3] = -1
    presyn[rng.random((C, F)) < 0.1] = top
    presyn[3, :4] = [top, top - 1, -1, 0]
    if perm == "f32":
        syn_perm = rng.random((C, F)).astype(np.float32)
        syn_perm[3, :3] = [-0.0, 1.0, np.float32(1e-38)]
    else:
        p_dt = {"u16": np.uint16, "u8": np.uint8}[perm]
        syn_perm = rng.integers(0, np.iinfo(p_dt).max + 1, (C, F)).astype(p_dt)
        syn_perm[3, :3] = [0, np.iinfo(p_dt).max, np.iinfo(p_dt).max // 2 + 1]
    n_hit = {"sparse": 3, "full_cap": Ac, "all_fills": 0}[hits]
    col_ids = np.full(Ac, C, np.int32)
    col_ids[:n_hit] = np.sort(rng.choice(C, n_hit, replace=False))
    if n_hit:
        col_ids[0] = 3  # the row of edge values; ascending order is not needed
    oh_b = jnp.asarray(col_ids[:, None] == np.arange(C)[None, :])
    got_pre, got_perm = jax.jit(tm_tpu._gather_rows_or)(
        (jnp.asarray(presyn), jnp.asarray(syn_perm)), oh_b)
    valid = col_ids < C
    for got, pool in ((got_pre, presyn), (got_perm, syn_perm)):
        got = np.asarray(got)
        assert got.dtype == pool.dtype and got.shape == (Ac, F)
        want = np.where(valid[:, None], pool[np.minimum(col_ids, C - 1)], 0).astype(pool.dtype)
        np.testing.assert_array_equal(got.view(f"u{pool.itemsize}"), want.view(f"u{pool.itemsize}"))
    if ids == "i16":  # ids < 2^24: the matmul the other narrow rows keep is exact too
        mm = tm_tpu._gather_rows_f32(jnp.asarray(presyn, jnp.float32), oh_b.astype(jnp.float32))
        np.testing.assert_array_equal(np.round(np.asarray(mm)).astype(np.int32),
                                      np.asarray(got_pre, np.int32))


def test_tm_parity_punishment_path(rows):
    """Alternating similar patterns so matching segments form in columns that
    then fail to activate -> predicted_segment_decrement punishment."""
    C, cfg = 48, TMConfig(
        cells_per_column=6, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=3, max_synapses_per_segment=8,
        new_synapse_count=5, predicted_segment_decrement=0.02,
        learn_cap=32,
    )
    rng = np.random.default_rng(31)
    X, Y = _pattern(rng, C, 6), _pattern(rng, C, 6)
    # overlapping variants of Y: some columns of Y activate, some don't
    Y2 = Y.copy(); Y2[:3] = _pattern(rng, C, 3)
    seq = ([X, Y] * 8 + [X, Y2] * 8) * 2
    _run_parity(C, cfg, seq)


def test_tm_parity_empty_and_full_columns(rows):
    """Edge cases: empty active set (raw=0) and all-columns-active steps."""
    C, cfg = 16, TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=6,
        new_synapse_count=4, learn_cap=80,
    )
    rng = np.random.default_rng(3)
    seq = [_pattern(rng, C, 3), np.arange(C), np.array([], np.int64),
           _pattern(rng, C, 3), np.arange(C), _pattern(rng, C, 3)] * 4
    _run_parity(C, cfg, seq)


# ---- the best-matching-segment mask, at the cases an index write hid ------
# (ISSUE 31: the mask was `zeros.at[arange(C), bm_k, bm_s].set(burst_match)`,
# which also wrote a False at [c, 0, 0] of every column that does not
# burst-match; it is a compare against the flat (k, s) iota now)

_EDGE_CFG = dict(cells_per_column=4, activation_threshold=4, min_threshold=2,
                 max_segments_per_cell=2, max_synapses_per_segment=8,
                 new_synapse_count=5, predicted_segment_decrement=0.02,
                 learn_cap=64, col_cap=16)


def _crafted_state(C, cfg, segments, prev_cells, prev_winners):
    """A state the step could have left behind: `segments` maps (c, k, s) to
    (number of synapses onto the previously active cells, permanence); the
    dendrite results (active / matching / potential counts) are derived from
    the pools the way the step's last stage derives them."""
    K = cfg.cells_per_column
    dom = tm_domain(cfg)
    st = _init_tm_state(C, cfg)
    st["syn_perm"] = np.zeros(st["syn_perm"].shape, dom.dtype)
    for c, k in prev_cells:
        st["prev_active"][c, k] = True
    for c, k in prev_winners:
        st["prev_winner"][c, k] = True
    ids = [c * K + k for c, k in prev_cells]
    for (c, k, s), (n, perm) in segments.items():
        st["presyn"][c, k, s, :n] = ids[:n]
        st["syn_perm"][c, k, s, :n] = dom.rate(perm)
        st["seg_last"][c, k, s] = 0
    pre = st["presyn"]
    on = (pre >= 0) & st["prev_active"].reshape(-1)[np.maximum(pre, 0)]
    pot = on.sum(-1)
    conn = (on & (st["syn_perm"] >= dom.threshold(cfg.connected_permanence))).sum(-1)
    exists = st["seg_last"] >= 0
    st["active_seg"] = exists & (conn >= cfg.activation_threshold)
    st["matching_seg"] = exists & (pot >= cfg.min_threshold)
    st["seg_pot"] = np.where(exists, pot, 0).astype(np.int32)
    st["tm_iter"] = np.int32(1)
    return st


_PREV = [(6, 0), (6, 1), (7, 0), (7, 1), (6, 2)]

_BM_CASES = {
    # column 0 bursts with no segment at all (the index write put a False at
    # its [0, 0]; it allocates there now) BESIDE column 1 whose best matching
    # segment IS flat index 0; column 3's best is the last flat index
    "none_beside_flat_zero": (
        {(1, 0, 0): (2, 0.3), (3, 3, 1): (3, 0.3), (5, 0, 1): (2, 0.3)},
        [0, 1, 3]),
    # equal potential counts: the first flat index wins — (1, 1) over (2, 0)
    # and over the weaker (0, 1); column 4 is predicted beside them
    "ties_take_the_first_max": (
        {(2, 0, 1): (2, 0.3), (2, 1, 1): (3, 0.3), (2, 2, 0): (3, 0.3),
         (4, 1, 0): (4, 0.6), (4, 2, 1): (3, 0.3)},
        [2, 4]),
    # every column burst-matches in the same tick, each at another (k, s)
    "every_column_at_once": (
        {(c, c % 4, (c // 4) % 2): (2 + c % 3, 0.3) for c in range(8)},
        list(range(8))),
    # a matching segment in a column that stays dark is punished, not taught
    "matching_but_not_active": (
        {(1, 2, 1): (3, 0.3), (5, 0, 0): (3, 0.3), (5, 3, 1): (2, 0.6)},
        [1]),
}


@pytest.fixture(params=[True, None], ids=["tpu_paths", "backend_paths"])
def compact_paths(request):
    old = tm_tpu.FORCE_TPU_PATHS
    tm_tpu.FORCE_TPU_PATHS = request.param
    jax.clear_caches()
    yield request.param
    tm_tpu.FORCE_TPU_PATHS = old
    jax.clear_caches()


@pytest.mark.parametrize("perm_bits", [0, 16], ids=["f32", "u16"])
@pytest.mark.parametrize("case", sorted(_BM_CASES))
def test_tm_parity_best_matching_segment_edges(case, perm_bits, rows, compact_paths):
    C = 8
    cfg = TMConfig(perm_bits=perm_bits, **_EDGE_CFG)
    segments, first = _BM_CASES[case]
    host = _crafted_state(C, cfg, segments, _PREV, _PREV[:3])
    if case == "every_column_at_once":
        assert host["matching_seg"].any((1, 2)).all() and not host["active_seg"].any()
    rng = np.random.default_rng(41)
    # the crafted tick, the same columns again (what it taught now predicts
    # or matches), then novelty over the taught pools
    seq = [np.array(first), np.array(first)] + [_pattern(rng, C, 3) for _ in range(6)]
    _run_parity(C, cfg, seq, host=host)


@pytest.mark.parametrize("case", sorted(_BM_CASES))
def test_best_matching_mask_is_the_oracles_choice(case, rows):
    """The mask itself, not only the state it leads to: one bit a
    burst-matching column, at the first maximum of the potential counts."""
    C = 8
    cfg = TMConfig(**_EDGE_CFG)
    segments, first = _BM_CASES[case]
    st = _crafted_state(C, cfg, segments, _PREV, _PREV[:3])
    active = np.zeros(C, bool)
    active[first] = True
    _, learn_mask, _, _, _ = tm_tpu._segment_learning_mask(
        cfg, jnp.asarray(active), jnp.asarray(st["active_seg"]),
        jnp.asarray(st["matching_seg"]), jnp.asarray(st["seg_pot"]),
        jnp.asarray(st["seg_last"]), jnp.bool_(True))
    want = active[:, None, None] & st["active_seg"]
    for c in np.flatnonzero(active & ~st["active_seg"].any((1, 2))):
        if st["matching_seg"][c].any():
            pot = np.where(st["matching_seg"][c], st["seg_pot"][c], -1)
            want[(c, *np.unravel_index(int(np.argmax(pot)), pot.shape))] = True
    np.testing.assert_array_equal(np.asarray(learn_mask), want)


@pytest.mark.parametrize("cut", ["learn_cap", "col_cap"])
def test_forms_agree_when_learning_overflows(compact_paths, monkeypatch, cut):
    """Past `learn_cap` the oracle (which has no cap) is no yardstick, but
    the two forms still are for each other: the first `learn_cap` learning
    segments learn and are stamped, the rest wait. The wide form names the
    stamped rows by a compare against the largest compacted id, the narrow
    one by the compacted ids' one-hot rows; same rows, same state. Past
    `col_cap` likewise: the first `col_cap` active columns enter the
    workspace — by index at wide rows, every slot a column and none a fill,
    by one-hot rows at narrow ones — and the rest learn nothing."""
    C, cfg = 32, TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=6,
        new_synapse_count=4, **({"learn_cap": 3, "col_cap": 8} if cut == "learn_cap"
                                else {"learn_cap": 32, "col_cap": 4}),
    )
    finals = {}
    for form, lanes in (("wide", 1), ("narrow", 1 << 30)):
        monkeypatch.setattr(tm_tpu, "WIDE_ROW_LANES", lanes)
        jax.clear_caches()
        dev = resident_form(
            {k: jnp.asarray(v) for k, v in _init_tm_state(C, cfg).items()}, cfg)
        rng = np.random.default_rng(5)
        for _ in range(40):
            active = np.zeros(C, bool)
            active[_pattern(rng, C, 6)] = True
            dev, _ = tm_step(dev, jnp.asarray(active), cfg, learn=True)
        finals[form] = jax.device_get(public_form(dev, cfg))
    jax.clear_caches()
    assert int(finals["wide"]["tm_overflow"]) > 0  # the cap really cut
    for key in TM_KEYS:
        np.testing.assert_array_equal(finals["wide"][key], finals["narrow"][key],
                                      err_msg=key)
