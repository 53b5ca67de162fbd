"""The TM kernel has two formulations of `_compact_ids` (nonzero vs the TPU's
top_k — ops/tm_tpu.py FORCE_TPU_PATHS). The default test platform is CPU,
which exercises nonzero; this file forces the TPU formulation and asserts
bit-identical behavior against the oracle, so the code that actually runs on
hardware is pinned by the same parity suite (SURVEY.md §4 item 2). Both
formulations across both forms of the step, every permanence domain:
tests/parity/test_tm_forms.py."""

import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.models.htm_model import HTMModel

from tests.parity.test_e2e_parity import exact_only, make_values, small_cfg


@pytest.fixture
def force_tpu_paths():
    old = tm_tpu.FORCE_TPU_PATHS
    tm_tpu.FORCE_TPU_PATHS = True
    # the strategy is baked into traced programs at jit time
    tm_tpu.tm_step.clear_cache()
    yield
    tm_tpu.FORCE_TPU_PATHS = old
    tm_tpu.tm_step.clear_cache()


@exact_only
def test_e2e_parity_with_tpu_paths(force_tpu_paths):
    cfg = small_cfg()
    cpu = HTMModel(cfg, seed=3, backend="cpu")
    tpu = HTMModel(cfg, seed=3, backend="tpu")
    vals = make_values(300, 1)
    for i in range(300):
        r_cpu = cpu.run(1_700_000_000 + 300 * i, float(vals[i, 0]))
        r_tpu = tpu.run(1_700_000_000 + 300 * i, float(vals[i, 0]))
        assert r_cpu.raw_score == pytest.approx(r_tpu.raw_score, abs=0.0), f"step {i}"


@exact_only
def test_compact_ids_matches_nonzero(force_tpu_paths):
    import jax.numpy as jnp

    rng = np.random.Generator(np.random.Philox(key=(5, 5)))
    for n, size in ((64, 8), (2048, 80), (8192, 32)):
        for density in (0.0, 0.01, 0.2, 1.0):
            mask = rng.random(n) < density
            got = np.asarray(tm_tpu._compact_ids(jnp.asarray(mask), size))
            want = np.flatnonzero(mask)[:size]
            want = np.concatenate([want, np.full(size - len(want), n)]).astype(np.int32)
            np.testing.assert_array_equal(got, want, err_msg=f"n={n} size={size} d={density}")
