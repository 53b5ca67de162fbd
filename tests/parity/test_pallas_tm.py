"""Pallas TM-learning megakernel parity (ops/pallas_tm.py).

RTAP_TM_SCATTER=pallas fuses the whole TM learning pass (alloc, reinforce,
grow/evict, punish, death, dendrite counts) into one kernel. These tests run
it in interpreter mode on the CPU test backend and assert bit-identical
behavior against the numpy oracle — full state, every step — through the
same branch-coverage sequences the workspace-path parity uses, in both
permanence domains and under vmap (the group_step shape).
"""

import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.config import ModelConfig, RDSEConfig, SPConfig, TMConfig
from rtap_tpu.models.htm_model import HTMModel


def small_cfg(perm_bits: int = 0, K: int = 8, S: int = 4, M: int = 16) -> ModelConfig:
    # col_cap pinned to the winner count: the megakernel's winner loops
    # unroll W = col_cap * K times, and the interpreter pays every
    # unrolled iteration at CPU-compile time — the default 40 is a
    # hardware-preset bound, pathological for interpreter tests
    return ModelConfig(
        rdse=RDSEConfig(size=128, active_bits=11, resolution=0.7),
        sp=SPConfig(columns=256, num_active_columns=10, perm_bits=perm_bits),
        tm=TMConfig(cells_per_column=K, activation_threshold=6, min_threshold=4,
                    max_segments_per_cell=S, max_synapses_per_segment=M,
                    new_synapse_count=8, learn_cap=48, col_cap=10,
                    perm_bits=perm_bits),
    )


@pytest.fixture
def pallas_scatter():
    tm_tpu.set_scatter_mode("pallas", interpret=True)
    yield
    tm_tpu.set_scatter_mode(None)


def _run_tm_parity(C, cfg, sequences, learn=True):
    from tests.parity.test_tm_parity import (
        TM_KEYS, _assert_state_equal, _init_tm_state,
    )
    import copy

    import jax.numpy as jnp

    from rtap_tpu.models.oracle.temporal_memory import TMOracle
    from rtap_tpu.ops.tm_tpu import from_kernel_layout, tm_step, to_kernel_layout

    host = _init_tm_state(C, cfg)
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
    assert TM_KEYS  # imported for completeness


@pytest.mark.quick
def test_tm_parity_megakernel_repeating_and_novel(pallas_scatter):
    """Repetition (reinforce/grow) + novelty (burst alloc, eviction): the
    branch mix of the crown-jewel TM parity, through the megernel."""
    C = 64
    cfg = TMConfig(
        cells_per_column=8, activation_threshold=3, min_threshold=2,
        max_segments_per_cell=4, max_synapses_per_segment=12,
        new_synapse_count=6, learn_cap=32, col_cap=6,
    )
    rng = np.random.default_rng(11)
    pats = [rng.choice(C, size=5, replace=False) for _ in range(4)]
    seq = pats * 8 + [rng.choice(C, size=5, replace=False) for _ in range(24)]
    _run_tm_parity(C, cfg, seq)


def test_tm_parity_megakernel_eviction_and_punish(pallas_scatter):
    """Tiny pools force LRU segment eviction + weakest-synapse eviction;
    alternating near-miss patterns drive the punishment path."""
    C = 32
    cfg = TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=6,
        new_synapse_count=4, predicted_segment_decrement=0.02, learn_cap=32,
        col_cap=5,
    )
    rng = np.random.default_rng(23)
    X, Y = (rng.choice(C, size=4, replace=False) for _ in range(2))
    Y2 = Y.copy()
    Y2[:2] = rng.choice(C, size=2, replace=False)
    seq = [rng.choice(C, size=4, replace=False) for _ in range(60)]
    seq += ([X, Y] * 6 + [X, Y2] * 6) * 2
    _run_tm_parity(C, cfg, seq)


def test_tm_parity_megakernel_edge_columns(pallas_scatter):
    """Empty and all-columns-active steps through the megakernel."""
    C = 16
    cfg = TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=6,
        new_synapse_count=4, learn_cap=80, col_cap=16,
    )
    rng = np.random.default_rng(3)
    seq = [rng.choice(C, 3, replace=False), np.arange(C), np.array([], np.int64),
           rng.choice(C, 3, replace=False), np.arange(C)] * 4
    _run_tm_parity(C, cfg, seq)


@pytest.mark.parametrize("perm_bits", [
    # f32 rides the slow tier: the three TM-level parity tests above cover
    # the f32 arithmetic already, and the 250-step interpreter e2e costs
    # ~70 s of the tier-1 budget per domain — u16 (the production domain,
    # with the round/astype epilogue worth covering end-to-end) stays
    pytest.param(0, marks=pytest.mark.slow),
    16,
])
def test_e2e_with_megakernel_matches_oracle(perm_bits, pallas_scatter):
    """Full pipeline (encode -> SP -> TM) with the megakernel: bit-exact
    vs the oracle through 250 learned steps incl. an anomaly spike."""
    import jax

    cfg = small_cfg(perm_bits)
    cpu = HTMModel(cfg, seed=7, backend="cpu")
    dev = HTMModel(cfg, seed=7, backend="tpu")
    t = np.arange(250)
    vals = (50 + 20 * np.sin(2 * np.pi * t / 50.0)
            + np.random.default_rng(3).normal(0, 2, 250)).astype(np.float32)
    vals[125] += 40
    for i in range(250):
        r1 = cpu.run(1_700_000_000 + 300 * i, float(vals[i]))
        r2 = dev.run(1_700_000_000 + 300 * i, float(vals[i]))
        assert r1.raw_score == r2.raw_score, f"step {i}"
    got = jax.device_get(dev._runner.state)
    for k in ("presyn", "syn_perm", "seg_last", "active_seg", "matching_seg",
              "seg_pot", "prev_active", "prev_winner"):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(cpu.state[k]), err_msg=k)
    assert int(got["tm_overflow"]) == 0


def test_megakernel_under_vmap(pallas_scatter):
    """group_step (vmapped tm_step) with the megakernel == without."""
    import jax
    import jax.numpy as jnp

    from rtap_tpu.models.state import init_state
    from rtap_tpu.ops.step import group_step, replicate_state

    cfg = small_cfg(16)
    G, n = 3, 50
    rng = np.random.default_rng(11)
    vals = (30 + 10 * rng.random((n, G))).astype(np.float32)

    def run():
        state = jax.device_put(replicate_state(init_state(cfg, seed=5), G))
        raws = []
        for i in range(n):
            ts = jnp.full(G, 1_700_000_000 + i, jnp.int32)
            state, raw = group_step(state, jnp.asarray(vals[i][:, None]), ts, cfg)
            raws.append(np.asarray(raw))
        return np.stack(raws), jax.device_get(state)

    raw_on, st_on = run()
    tm_tpu.set_scatter_mode(None)  # back to the process default (matmul)
    raw_off, st_off = run()
    np.testing.assert_array_equal(raw_on, raw_off)
    for k in ("presyn", "syn_perm", "seg_pot", "active_seg"):
        np.testing.assert_array_equal(st_on[k], st_off[k], err_msg=k)


def test_megakernel_rejects_incompatible_strategies(pallas_scatter):
    """forward dendrite and compact sweep cannot combine with the
    megakernel — tm_step must refuse loudly, not silently diverge."""
    import jax.numpy as jnp

    from tests.parity.test_tm_parity import _init_tm_state

    cfg = TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=6,
        new_synapse_count=4, learn_cap=16, col_cap=4,
    )
    C = 16
    state = {k: jnp.asarray(v) for k, v in _init_tm_state(C, cfg).items()}
    active = jnp.zeros(C, bool)
    tm_tpu.set_sweep_mode("compact")
    try:
        with pytest.raises(ValueError, match="SWEEP=compact"):
            tm_tpu.tm_step(
                tm_tpu.to_kernel_layout(state, cfg), active, cfg, learn=True)
    finally:
        tm_tpu.set_sweep_mode(None)
    tm_tpu.set_dendrite_mode("forward")
    try:
        with pytest.raises(ValueError, match="DENDRITE=forward"):
            tm_tpu.tm_step(
                tm_tpu.to_kernel_layout(state, cfg), active, cfg, learn=True)
    finally:
        tm_tpu.set_dendrite_mode(None)


def test_megakernel_guards_reject_oversized_shapes(pallas_scatter):
    """Interpreter-size / winner-unroll / VMEM guards fail loudly instead
    of hanging in the interpreter or deep inside Mosaic."""
    import jax.numpy as jnp

    from rtap_tpu.config import nab_preset
    from rtap_tpu.models.state import init_state
    from rtap_tpu.ops.tm_tpu import to_kernel_layout, tm_step

    cfg = nab_preset()
    st = to_kernel_layout(
        {k: jnp.asarray(v) for k, v in init_state(cfg, seed=0).items()
         if k not in ("potential", "perm", "boost", "overlap_duty",
                      "active_duty", "sp_iter", "enc_offset", "enc_bound",
                      "enc_resolution")},
        cfg.tm)
    active = jnp.zeros(cfg.sp.columns, bool)
    with pytest.raises(ValueError, match="INTERPRETER|winner-list|VMEM"):
        tm_step(st, active, cfg.tm, learn=True)


@pytest.mark.quick
def test_pallas_mode_actually_dispatches_tm_learn_pallas(pallas_scatter, monkeypatch):
    """The twin-registry pin for tm_learn_pallas: RTAP_TM_SCATTER=pallas
    must route the learning pass through the megakernel entry point —
    if the mode switch silently fell back to the workspace path, every
    'pallas parity' test above would be vacuously green."""
    import rtap_tpu.ops.pallas_tm as pallas_tm

    calls = []
    real = pallas_tm.tm_learn_pallas

    def counting(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(pallas_tm, "tm_learn_pallas", counting)
    C = 32
    cfg = TMConfig(
        cells_per_column=4, activation_threshold=2, min_threshold=1,
        max_segments_per_cell=2, max_synapses_per_segment=8,
        new_synapse_count=4, learn_cap=16, col_cap=4,
    )
    _run_tm_parity(C, cfg, [np.arange(4), np.arange(4)])
    assert calls, "pallas scatter mode never reached tm_learn_pallas"
