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
from rtap_tpu.ops import tm_tpu
from rtap_tpu.ops.tm_tpu import from_kernel_layout, tm_step, to_kernel_layout

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
                        1 if request.param == "wide" else 1 << 30)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


def _run_parity(C, cfg, sequences, learn=True):
    host = _init_tm_state(C, cfg)
    # the public [C, K, S, M] layout crosses the boundary via the same
    # reshape adapters ops/step.py uses
    dev = to_kernel_layout(
        {k: jnp.asarray(v) for k, v in copy.deepcopy(host).items()}, cfg)
    oracle = TMOracle(host, cfg)
    for step, cols in enumerate(sequences):
        active = np.zeros(C, bool)
        active[cols] = True
        raw_host = oracle.compute(active, learn=learn)
        dev, raw_dev = tm_step(dev, jnp.asarray(active), cfg, learn=learn)
        assert abs(raw_host - float(raw_dev)) < 1e-6, f"raw score step {step}"
        _assert_state_equal(host, from_kernel_layout(dev, cfg), step)


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


@pytest.mark.quick
@pytest.mark.parametrize("S,M,wide", [(2, 6, False), (16, 32, True)],
                         ids=["narrow", "wide"])
def test_tm_parity_explicit_layouts(S, M, wide):
    """Full state parity in BOTH forms where the shape itself picks the form
    (the other tests move the line under one shape): 48 lanes a row, and
    2,048."""
    C, cfg = 32, TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=S, max_synapses_per_segment=M,
        new_synapse_count=4, learn_cap=32,
    )
    assert tm_tpu.wide_rows(cfg) == wide
    rng = np.random.default_rng(29)
    seq = [_pattern(rng, C, 4) for _ in range(60)]
    _run_parity(C, cfg, seq)


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
