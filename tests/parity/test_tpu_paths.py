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


def _lowered_text(cfg, program: str) -> str:
    """StableHLO of `ops/step.py`'s `program` (learning on) at a batch of 4,
    traced afresh in whatever formulations are forced."""
    import jax
    import jax.numpy as jnp

    import rtap_tpu.ops.step as step
    from rtap_tpu.models.state import init_state

    G, lead = 4, (2,) if program == "chunk_step" else ()
    state = {k: jax.ShapeDtypeStruct((G, *np.shape(v)), np.asarray(v).dtype)
             for k, v in init_state(cfg, 0).items()}
    vals = jax.ShapeDtypeStruct((*lead, G, cfg.n_fields), jnp.float32)
    ts = jax.ShapeDtypeStruct((*lead, G), jnp.int32)
    jax.clear_caches()  # a program traced with the backend's forms must not serve
    try:
        return getattr(step, program).lower(state, vals, ts, cfg, learn=True).as_text()
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("program", ["chunk_step", "group_step"])
@pytest.mark.parametrize("preset", ["cluster", "scaled32"])
def test_cluster_step_lowers_with_no_scatter_and_no_gather(force_tpu_paths, preset, program):
    """The CPU-only twin of tests/integration/test_chip_compile.py's pins
    (no TPU compiler needed): in the formulations the chip runs, the fused
    step of both cluster presets lowers to no `stablehlo.scatter` and no
    `stablehlo.gather` — an index list becomes a mask by compare (ISSUE 31;
    the gathers went with ISSUEs 26 and 28)."""
    from rtap_tpu.config import cluster_preset, scaled_cluster_preset

    cfg = cluster_preset() if preset == "cluster" else scaled_cluster_preset(32)
    text = _lowered_text(cfg, program)
    assert "stablehlo.scatter" not in text
    assert "stablehlo.gather" not in text
    assert text.count("stablehlo.compare") > 50  # a whole step was lowered


@pytest.mark.parametrize("program", ["chunk_step", "group_step"])
@pytest.mark.parametrize("preset", ["cluster", "scaled32", "wide"])
def test_step_lowers_membership_with_no_candidate_axis_and_no_division(force_tpu_paths, preset, program):
    """The TM's membership test is element-wise over the pool (ISSUE 36): a
    static chain of selects over the Ac packed columns and a shift/mask
    decode, so nothing stands between the pool and the reduce over its
    synapse lanes. Held by the lowering, at both cluster presets and at a
    wide-row shape of a tiny size: no tensor carries a trailing Ac axis
    behind the pool's dims ([C, K*S*M, Ac] flat; at wide rows [C, M, K*S, Ac]
    in a chunk's scan and [C, K, S, M, Ac] in a one-tick program — the
    parent held 23 at `cluster_preset`), and no `stablehlo.divide` or
    `stablehlo.remainder` works on a pool-shaped operand (6 and 9 there).
    The mechanism engages always or never; this says which."""
    import re

    from rtap_tpu.config import cluster_preset, scaled_cluster_preset
    from tests.parity.test_tm_forms import form_cfg

    cfg = {"cluster": cluster_preset, "scaled32": lambda: scaled_cluster_preset(32),
           "wide": lambda: form_cfg("wide", 16)}[preset]()
    tm = cfg.tm
    K, S, M = tm.cells_per_column, tm.max_segments_per_cell, tm.max_synapses_per_segment
    assert tm_tpu.wide_rows(tm) == (preset == "wide")
    pool = (f"{cfg.sp.columns}x{K * S * M}" if preset != "wide" else
            f"{cfg.sp.columns}x{M}x{K * S}" if program == "chunk_step" else
            f"{cfg.sp.columns}x{K}x{S}x{M}")
    text = _lowered_text(cfg, program)
    assert f"x{pool}x" in text  # the pools are in the program, in this spelling
    assert not re.findall(rf"tensor<(?:\d+x)*{pool}x{tm.col_cap}x\w+>", text)
    on_pool = [ln for ln in text.splitlines()
               if ("stablehlo.divide" in ln or "stablehlo.remainder" in ln) and f"x{pool}x" in ln]
    assert not on_pool, on_pool[:3]
