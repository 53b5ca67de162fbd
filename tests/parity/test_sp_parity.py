"""Oracle-vs-device SP parity (SURVEY.md §4 item 2, the NuPIC
spatial_pooler_compatibility_test pattern): run the numpy oracle and the
jitted kernel side by side from the same init_state and assert bit-identical
active columns, permanences, and duty cycles every step.
"""

import copy

import jax.numpy as jnp
import numpy as np
import pytest

from rtap_tpu.config import ModelConfig, RDSEConfig, SPConfig
from rtap_tpu.models.oracle.spatial_pooler import sp_compute
from rtap_tpu.models.state import init_state
from rtap_tpu.ops.sp_tpu import sp_step

SP_KEYS = ("perm", "boost", "overlap_duty", "active_duty", "sp_iter", "potential")


def _device_state(state):
    return {k: jnp.asarray(state[k]) for k in SP_KEYS}


def _run_parity(cfg: ModelConfig, n_steps: int, learn: bool, atol=0.0):
    rng = np.random.default_rng(7)
    host = init_state(cfg, seed=3)
    dev = _device_state(copy.deepcopy(host))
    n_in = cfg.input_size
    w = max(1, int(0.05 * n_in))
    for step in range(n_steps):
        sdr = np.zeros(n_in, bool)
        sdr[rng.choice(n_in, size=w, replace=False)] = True
        host_active = sp_compute(host, sdr, cfg.sp, learn=learn)
        dev, dev_active = sp_step(dev, jnp.asarray(sdr), cfg.sp, learn=learn)
        np.testing.assert_array_equal(host_active, np.asarray(dev_active), err_msg=f"step {step}")
        if atol == 0.0:
            np.testing.assert_array_equal(host["perm"], np.asarray(dev["perm"]), err_msg=f"step {step}")
            np.testing.assert_array_equal(host["overlap_duty"], np.asarray(dev["overlap_duty"]))
            np.testing.assert_array_equal(host["active_duty"], np.asarray(dev["active_duty"]))
        else:
            np.testing.assert_allclose(host["perm"], np.asarray(dev["perm"]), atol=atol)
    assert int(host["sp_iter"]) == int(dev["sp_iter"]) == (n_steps if learn else 0)


@pytest.mark.quick
@pytest.mark.parametrize("learn", [True, False])
def test_sp_parity_small(learn):
    cfg = ModelConfig(
        rdse=RDSEConfig(size=64, active_bits=5, resolution=0.5),
        sp=SPConfig(columns=128, num_active_columns=8),
    )
    _run_parity(cfg, n_steps=100, learn=learn)


def test_sp_parity_nab_scale():
    cfg = ModelConfig(sp=SPConfig(columns=2048, num_active_columns=40))
    _run_parity(cfg, n_steps=20, learn=True)


def test_sp_parity_with_boost():
    # boost>0 exercises the exp path; fp exp may differ in the last ulp across
    # backends, but the 1/256-quantized inhibition score must keep winner
    # selection identical, and permanences drift only via winner differences.
    cfg = ModelConfig(
        rdse=RDSEConfig(size=64, active_bits=5, resolution=0.5),
        sp=SPConfig(columns=128, num_active_columns=8, boost_strength=2.0),
    )
    _run_parity(cfg, n_steps=60, learn=True, atol=1e-6)


# ---- inhibition at the cases an index write hid (ISSUE 31) ----------------
# The winner mask was `zeros(C).at[top_k indices].set(True)`; it is
# `score >= the least of the k largest scores` now, which holds only because
# the scores are distinct a column. Overlaps are crafted, so the twins are
# called on their own: ties across the k-th place, ties everywhere, a
# stimulus threshold that cuts a winner, k = 1 and k = C.

def _overlaps(case: str, C: int) -> np.ndarray:
    rng = np.random.default_rng(13)
    if case == "tie_across_kth_place":
        ov = np.full(C, 2, np.int32)
        ov[[5, 9]] = 7          # two clear winners
        ov[[40, 3, 17, 60]] = 4  # four columns tie for the remaining places
        return ov
    if case == "all_equal":
        return np.full(C, 3, np.int32)
    if case == "all_zero":
        return np.zeros(C, np.int32)
    if case == "threshold_cuts_a_winner":
        ov = np.zeros(C, np.int32)
        ov[[1, 8, 30]] = [5, 2, 1]  # below the threshold of 2: column 30 and every 0
        return ov
    if case == "winners_at_both_ends":
        ov = rng.integers(0, 4, C).astype(np.int32)
        ov[[0, C - 1]] = 9
        return ov
    raise AssertionError(case)


_INHIBIT_CASES = ["tie_across_kth_place", "all_equal", "all_zero",
                  "threshold_cuts_a_winner", "winners_at_both_ends"]


@pytest.mark.parametrize("boost", [0.0, 2.0], ids=["no_boost", "boost"])
@pytest.mark.parametrize("k", [1, 4, 64], ids=["k1", "k4", "k_all"])
@pytest.mark.parametrize("case", _INHIBIT_CASES)
def test_sp_inhibit_edges_match_the_oracle(case, k, boost):
    from rtap_tpu.models.oracle import spatial_pooler as oracle_sp
    from rtap_tpu.ops.sp_tpu import sp_inhibit

    C = 64
    cfg = SPConfig(columns=C, num_active_columns=k, boost_strength=boost,
                   stimulus_threshold=2 if case == "threshold_cuts_a_winner" else 0)
    overlap = _overlaps(case, C)
    # boost factors that keep the quantized scores far from a .5 boundary
    factors = np.where(np.arange(C) % 2 == 0, 1.0, 1.5).astype(np.float32)
    want = oracle_sp.sp_inhibit(overlap, factors, cfg)
    got = np.asarray(sp_inhibit(jnp.asarray(overlap), jnp.asarray(factors), cfg))
    np.testing.assert_array_equal(got, want)
    if case == "threshold_cuts_a_winner":
        assert got.sum() == min(k, 2) and not got[30]
    elif case == "all_zero" or boost == 0.0:
        assert got.sum() == k  # a tie never lets a (k+1)-th column through
    if case == "tie_across_kth_place" and k == 4 and boost == 0.0:
        assert sorted(np.flatnonzero(got)) == [3, 5, 9, 17]  # lowest index wins a tie
