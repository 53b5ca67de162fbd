"""The TM step has two forms and `tm_tpu.wide_rows(cfg)` picks one from the
static shape: below `WIDE_ROW_LANES` synapse lanes a pool row, one-hot matmul
moves over flat pools; at or above it, indexed moves over [C, M, K*S] pools
(a column's row contiguous), whatever the program's length (`resident_form` /
`public_form` convert a public [C, K, S, M] tree at a program's boundary).
Within the narrow-row form `tm_tpu.gather_by_select(cfg)` picks the workspace
gather the same way: a compare-select reduce in the pools' own types where a
row fills whole 128-lane tiles (128, 384, 512 lanes here), the one-hot matmul
where it does not (192). All are held to the numpy oracle here, end to end
(encode -> SP -> TM -> raw score), bit for bit, in every permanence domain,
under both `_compact_ids` formulations (`FORCE_TPU_PATHS`: the one the chip
runs and the one the CPU backend picks), learning and inferring, and through
the stream-group programs (`group_step`, `chunk_step`).

Either form compacts the learning segments out of the workspace only where
`learn_cap` cuts rows (`tm_tpu.compacts_learning_rows(cfg)`: L < col_cap*K*S);
where it does not — L = R2 as the node presets have it, L > R2 as at 32
columns — reinforce and growth run on the workspace's rows in place. The
`-equal` and `-over` shapes of `form_cfg` hold that side to the same oracle
through the same tests.

Nothing but the shape selects a form: no environment variable, no setter
(`test_no_environment_variable_selects_a_form`,
`test_the_learn_cap_compaction_engages_only_where_it_cuts`)."""

import inspect
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.config import (
    DateConfig, ModelConfig, RDSEConfig, SPConfig, TMConfig, cluster_preset, nab_preset,
    node_preset, scaled_cluster_preset,
)
from rtap_tpu.models.htm_model import HTMModel
from rtap_tpu.models.state import init_state

from tests.parity.test_e2e_parity import exact_only, make_values

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STATE_KEYS = ("perm", "boost", "overlap_duty", "active_duty", "presyn", "syn_perm",
              "seg_last", "active_seg", "matching_seg", "seg_pot", "prev_active",
              "prev_winner", "tm_iter", "enc_offset")


def form_cfg(rows: str, perm_bits: int) -> ModelConfig:
    """narrow: 256 columns x 8 cells x 4 segments x 16 synapses = 512 lanes a
    row; wide: 64 columns (small, so the oracle stays fast) x 8 x 8 x 32 =
    2,048 lanes, the first width on the wide side of the line; lanes<n>:
    narrow rows of n lanes at 64 columns — 128 (one tile), 192 (the cluster
    presets': a tile and a half), 384 (the node presets': three tiles).

    As named, `learn_cap` 48 cuts the workspace's R2 = col_cap*K*S rows (the
    default `col_cap` 40: 640 rows and more) and the step compacts. With the
    suffix `-equal` the workspace is as small as the shape allows (`col_cap`
    = the k active columns) and `learn_cap` = R2, the node presets' case;
    `-over` states 16 rows more than there are, as `scaled_cluster_preset(32)`
    does (64 > 48): the learning rows are the workspace's in place."""
    shape, _, cap = rows.partition("-")
    columns, k, S, M = {"narrow": (256, 10, 4, 16), "wide": (64, 6, 8, 32),
                        "lanes128": (64, 6, 2, 8), "lanes192": (64, 6, 2, 12),
                        "lanes384": (64, 6, 4, 12)}[shape]
    caps = {"": {"learn_cap": 48},
            "equal": {"learn_cap": k * 8 * S, "col_cap": k},
            "over": {"learn_cap": k * 8 * S + 16, "col_cap": k}}[cap]
    return ModelConfig(
        rdse=RDSEConfig(size=128, active_bits=11, resolution=0.7),
        date=DateConfig(time_of_day_width=7, time_of_day_size=18, weekend_width=3),
        sp=SPConfig(columns=columns, num_active_columns=k, perm_bits=perm_bits),
        tm=TMConfig(cells_per_column=8, activation_threshold=6, min_threshold=4,
                    max_segments_per_cell=S, max_synapses_per_segment=M,
                    new_synapse_count=8, perm_bits=perm_bits, **caps),
    )


#: the shapes of `form_cfg` whose workspace gather is the compare-select reduce
SELECT_ROWS = ("narrow", "lanes128", "lanes384")

#: the shapes whose `learn_cap` cuts nothing: L = R2 and L > R2 at 192 lanes
#: (the one-hot matmul gather), 384 lanes (the select gather) and wide rows
IN_PLACE_ROWS = tuple(f"{shape}-{cap}" for shape in ("lanes192", "lanes384", "wide")
                      for cap in ("equal", "over"))


def workspace_rows(tm: TMConfig) -> int:
    """R2: the segments of `col_cap` columns, all that can learn in one tick."""
    return tm.col_cap * tm.cells_per_column * tm.max_segments_per_cell


def holds_the_shapes_forms(rows: str, tm: TMConfig) -> None:
    """All three predicates say of `form_cfg(rows)` what its name does."""
    shape, _, cap = rows.partition("-")
    assert tm_tpu.wide_rows(tm) == (shape == "wide")
    assert tm_tpu.gather_by_select(tm) == (shape in SELECT_ROWS)
    assert tm_tpu.compacts_learning_rows(tm) == (not cap)
    r2 = workspace_rows(tm)
    assert {"": tm.learn_cap < r2, "equal": tm.learn_cap == r2,
            "over": tm.learn_cap > r2}[cap]


@pytest.fixture(scope="module", params=[True, False], ids=["tpu_paths", "cpu_paths"])
def tpu_paths(request):
    old = tm_tpu.FORCE_TPU_PATHS
    tm_tpu.FORCE_TPU_PATHS = request.param
    jax.clear_caches()  # the formulation is baked into traced programs
    yield request.param
    tm_tpu.FORCE_TPU_PATHS = old
    jax.clear_caches()


@exact_only
@pytest.mark.parametrize("learn", ["learning", "inferring"])
@pytest.mark.parametrize("perm_bits", [0, 16, 8])
@pytest.mark.parametrize("rows", ["narrow", "wide", "lanes128", "lanes192", "lanes384",
                                  *IN_PLACE_ROWS])
def test_form_equals_the_oracle_end_to_end(tpu_paths, rows, perm_bits, learn):
    cfg = form_cfg(rows, perm_bits)
    holds_the_shapes_forms(rows, cfg.tm)
    cpu = HTMModel(cfg, seed=17, backend="cpu")
    dev = HTMModel(cfg, seed=17, backend="tpu")
    n = 160
    # "inferring": a learned warm-up, then ticks that must leave the pools alone
    learn_until = n if learn == "learning" else 110
    vals = make_values(n, 1, seed=29)
    for i in range(n):
        ts, v = 1_700_000_000 + 300 * i, float(vals[i, 0])
        r_cpu = cpu.run(ts, v, learn=i < learn_until)
        r_dev = dev.run(ts, v, learn=i < learn_until)
        assert r_cpu.raw_score == pytest.approx(r_dev.raw_score, abs=0.0), f"step {i}"
    state = jax.device_get(dev._runner.state)
    for k in STATE_KEYS:
        np.testing.assert_array_equal(np.asarray(state[k]), np.asarray(cpu.state[k]), err_msg=k)
    assert int(state["tm_overflow"]) == 0
    assert (np.asarray(state["presyn"]) >= 0).sum() > 100  # it really learned


@exact_only
@pytest.mark.parametrize("program", ["group_step", "chunk_step"])
@pytest.mark.parametrize("rows", ["lanes128", "lanes192", "lanes384", "wide", *IN_PLACE_ROWS])
def test_gather_equals_the_oracle_through_the_group_programs(rows, program):
    """Both narrow-row gathers, and the wide form's indexed one, with the
    learning rows compacted and in place, under the programs the service
    runs — vmapped over a group's streams (at wide rows on the public
    layout), and inside the chunk's scan (there on [C, M, K*S] pools): three
    streams of one group against three oracles, raw scores tick by tick and
    every leaf after."""
    from rtap_tpu.models.htm_model import oracle_record_step
    from rtap_tpu.models.oracle.temporal_memory import TMOracle
    from rtap_tpu.ops.step import chunk_step, group_step, replicate_state

    cfg = form_cfg(rows, 16)
    holds_the_shapes_forms(rows, cfg.tm)
    G, T, n = 3, 8, 96
    gstate = jax.device_put(replicate_state(init_state(cfg, seed=5), G))
    oracles = []
    for _ in range(G):
        st = init_state(cfg, seed=5)
        oracles.append((st, TMOracle(st, cfg.tm)))
    vals = np.stack([make_values(n, 1, seed=31 + g)[:, 0] for g in range(G)], 1)
    ts = 1_700_000_000 + 300 * np.arange(n, dtype=np.int32)
    raws = []
    if program == "group_step":
        for i in range(n):
            gstate, raw = group_step(gstate, vals[i][:, None], np.full(G, ts[i]), cfg)
            raws.append(np.asarray(raw))
    else:
        for c in range(0, n, T):
            gstate, raw = chunk_step(gstate, vals[c:c + T][:, :, None],
                                     np.repeat(ts[c:c + T, None], G, 1), cfg)
            raws.extend(np.asarray(raw))
    for i in range(n):
        for g, (st, tm) in enumerate(oracles):
            want = oracle_record_step(cfg, st, tm, vals[i, g:g + 1], int(ts[i]), True)
            assert float(want) == float(raws[i][g]), f"tick {i} stream {g}"
    dev = jax.device_get(gstate)
    for k in STATE_KEYS:
        for g, (st, _) in enumerate(oracles):
            np.testing.assert_array_equal(np.asarray(dev[k][g]), np.asarray(st[k]),
                                          err_msg=f"{k} stream {g}")
    assert int(np.asarray(dev["tm_overflow"]).sum()) == 0
    assert (np.asarray(dev["presyn"]) >= 0).sum() > 100 * G  # they really learned


@pytest.mark.parametrize("home", ["numpy", "device"])
@pytest.mark.parametrize("lead", ["one_stream", "a_group"])
@pytest.mark.parametrize("rows", ["wide", "lanes192", "lanes384"])
def test_layout_adapters_round_trip_leaf_for_leaf(rows, lead, home):
    """`public_form(resident_form(s)) == s`, every leaf's values, shape and
    type, on a learned state (so the pools hold distinct values wherever a
    transposition could misplace one) — one stream's state, as `fused_step`
    hands it over, and a group's with its leading G axis, as `group_step` /
    `chunk_step` do; with the leaves numpy's (the host-side view a
    checkpoint or a fresh row goes through) and the device's, as
    `resident_leaf` promises both. In between, the pools have the kernel's
    shape: [C, K*S*M] at narrow rows, [C, M, K*S] at wide ones."""
    from rtap_tpu.ops.step import replicate_state

    cfg = form_cfg(rows, 16)
    model = HTMModel(cfg, seed=23, backend="cpu")
    vals = make_values(120, 1, seed=3)
    for i in range(120):
        model.run(1_700_000_000 + 300 * i, float(vals[i, 0]))
    state = {k: np.asarray(v) for k, v in model.state.items()}
    assert (state["presyn"] >= 0).sum() > 100
    # distinct values in every slot, so a misplaced one cannot hide
    state["presyn"] = np.arange(state["presyn"].size, dtype=state["presyn"].dtype
                                ).reshape(state["presyn"].shape)
    if lead == "a_group":
        state = replicate_state(state, 3)
        state["presyn"][1] += 1
    tm = cfg.tm
    K, S, M = tm.cells_per_column, tm.max_segments_per_cell, tm.max_synapses_per_segment
    C = cfg.sp.columns
    handed = jax.device_put(state) if home == "device" else state
    kernel = tm_tpu.resident_form(handed, tm)
    leaf_type = jax.Array if home == "device" else np.ndarray
    assert all(isinstance(kernel[k], leaf_type) for k in tm_tpu._KERNEL_KEYS)
    g = (3,) if lead == "a_group" else ()
    want = (C, M, K * S) if rows == "wide" else (C, K * S * M)
    assert kernel["presyn"].shape == kernel["syn_perm"].shape == g + want
    assert kernel["seg_last"].shape == kernel["seg_pot"].shape == g + (C, K * S)
    assert tm_tpu.kernel_resident(kernel) and not tm_tpu.kernel_resident(handed)
    # the kernel's slot [c, m, k*S + s] is the public one [c, k, s, m]
    pub = state["presyn"].reshape(*g, C, K * S, M)
    if rows == "wide":
        np.testing.assert_array_equal(np.asarray(kernel["presyn"])[..., 5, 7, 9],
                                      pub[..., 5, 9, 7])
    back = tm_tpu.public_form(kernel, tm)
    assert back.keys() == state.keys()
    for k, v in state.items():
        assert back[k].shape == v.shape and back[k].dtype == v.dtype, k
        np.testing.assert_array_equal(np.asarray(back[k]), v, err_msg=k)


def _another_forms_layout(handed: str, public: dict, turned: dict, cfg) -> dict:
    """A TM state as a caller gets it wrong, from the public tree and the
    resident one of the same values."""
    C = cfg.sp.columns
    tm = cfg.tm
    KS, M = tm.cells_per_column * tm.max_segments_per_cell, tm.max_synapses_per_segment
    pools = ("presyn", "syn_perm")
    return {
        "public": public,
        # the other side of the line's pools: flat rows handed to the wide
        # step, [C, M, K*S] ones to the narrow
        "other_rows_pools": {**turned, **{
            k: (public[k].reshape(C, -1) if tm_tpu.wide_rows(tm)
                else np.swapaxes(public[k].reshape(C, KS, M), 1, 2)) for k in pools}},
        # half turned: pools resident and a segment tensor public, and the reverse
        "public_segments": {**turned, "seg_last": public["seg_last"]},
        "public_pools": {**public, "seg_last": turned["seg_last"]},
    }[handed]


@pytest.mark.parametrize("handed", ["public", "other_rows_pools", "public_segments",
                                    "public_pools"])
@pytest.mark.parametrize("rows", ["wide", "narrow", "wide-equal", "wide-over"])
def test_tm_step_refuses_a_state_in_another_forms_layout(rows, handed):
    """Either form runs on ONE layout — `resident_form`'s — and says so when
    handed another, instead of computing on misread axes: the public
    [C, K, S, M] tree (ops/step.py converts it at a program's boundary; no
    program length makes the kernel take it), the pools of the other side of
    the line, and a state half turned — where `learn_cap` cuts the
    workspace's rows and where the rows learn in place."""
    from tests.parity.test_tm_parity import TM_KEYS

    cfg = form_cfg(rows, 16)
    shape = rows.partition("-")[0]
    st = init_state(cfg, 3)
    public = {k: np.asarray(st[k]) for k in TM_KEYS}
    active = np.zeros(cfg.sp.columns, bool)
    turned = tm_tpu.resident_form(public, cfg.tm)
    with pytest.raises(ValueError, match=rf"{shape} pool rows.*resident_form"):
        tm_tpu.tm_step(_another_forms_layout(handed, public, turned, cfg), active,
                       cfg.tm, learn=True)
    tm_tpu.tm_step(turned, active, cfg.tm, learn=True)


PRESETS = {"node3": lambda: node_preset(3), "scaled32": lambda: scaled_cluster_preset(32),
           "cluster": cluster_preset, "nab": lambda: nab_preset(0.0, 100.0)}


@pytest.mark.parametrize("preset, compacts", [("node3", False), ("scaled32", False),
                                              ("cluster", True), ("nab", True)])
def test_the_learn_cap_compaction_engages_only_where_it_cuts(preset, compacts):
    """`compacts_learning_rows` per preset: the node model's cap is the
    structural bound (320 = 10 x 8 x 4) and the 32-column model's is over it
    (64 > 3 x 8 x 2), so neither compacts; `cluster_preset` (64 < 160) and
    `nab_preset` (1,280 < 20,480) do. The answer is a function of the
    `TMConfig` and nothing else: one argument, no setter in the module, no
    read of the environment (`test_no_environment_variable_selects_a_form`
    lowers the 32-column step, which takes the in-place side, under a loud
    environment)."""
    tm = PRESETS[preset]().tm
    assert tm_tpu.compacts_learning_rows(tm) == compacts
    assert compacts == (tm.learn_cap < workspace_rows(tm))
    assert list(inspect.signature(tm_tpu.compacts_learning_rows).parameters) == ["cfg"]
    assert not [n for n in vars(tm_tpu) if n.startswith(("set_", "use_", "select_"))]
    assert not re.search(r"os\.environ|getenv|^import os", inspect.getsource(tm_tpu), re.M)


def _lowered_tm_step(cfg) -> str:
    from tests.parity.test_tm_parity import TM_KEYS

    st = init_state(cfg, 0)
    state = tm_tpu.resident_form({k: np.asarray(st[k]) for k in TM_KEYS}, cfg.tm)
    return tm_tpu.tm_step.lower(state, np.zeros(cfg.sp.columns, bool), cfg.tm,
                                learn=True).as_text()


def test_the_node_step_holds_no_compaction_grid_and_no_top_k_over_its_rows(monkeypatch):
    """`tm_step` of `node_preset(3)` as lowered, in the formulation the chip
    runs: no tensor whose two minor dimensions are (learn_cap, R2) — the
    one-hot grid, its `any`, its where-sums — and no `top_k` over the R2
    learning flags. The patterns bite: with the predicate patched to True
    the same step holds both."""
    cfg = node_preset(3)
    tm = cfg.tm
    r2 = workspace_rows(tm)
    assert tm.learn_cap == r2 == 320
    grid = re.compile(rf"tensor<(?:\d+x)*{tm.learn_cap}x{r2}x\w+>")
    top_k = re.compile(rf"top_k[^\n]*tensor<{r2}xi32>")
    monkeypatch.setattr(tm_tpu, "FORCE_TPU_PATHS", True)
    jax.clear_caches()  # the forms are read at trace time
    try:
        text = _lowered_tm_step(cfg)
        assert not grid.search(text) and not top_k.search(text)
        assert "top_k" in text  # the column lists still take theirs
        monkeypatch.setattr(tm_tpu, "compacts_learning_rows", lambda cfg: True)
        jax.clear_caches()
        compacting = _lowered_tm_step(cfg)
        assert grid.search(compacting) and top_k.search(compacting)
        assert len(compacting.splitlines()) > len(text.splitlines()) + 50
    finally:
        monkeypatch.undo()
        jax.clear_caches()


_LOWER = """
import hashlib
import jax, jax.numpy as jnp, numpy as np
import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.config import scaled_cluster_preset
from rtap_tpu.models.state import init_state
from tests.parity.test_tm_parity import TM_KEYS
cfg = scaled_cluster_preset(32)
st = init_state(cfg, 0)
state = tm_tpu.resident_form({k: jnp.asarray(st[k]) for k in TM_KEYS}, cfg.tm)
text = tm_tpu.tm_step.lower(state, jnp.zeros(cfg.sp.columns, bool), cfg.tm, learn=True).as_text()
print("SHA", hashlib.sha256(text.encode()).hexdigest(), len(text))
"""


def test_no_environment_variable_selects_a_form():
    """The strategy variables older builds read at import change nothing:
    tm_step at cluster-32's shape lowers to the same text with all of them
    set as with none."""
    clean = {k: v for k, v in os.environ.items() if not k.startswith("RTAP_TM_")}
    clean["PYTHONPATH"] = REPO
    loud = dict(clean, RTAP_TM_SCATTER="indexed", RTAP_TM_LAYOUT="aos",
                RTAP_TM_SWEEP="compact", RTAP_TM_DENDRITE="forward",
                RTAP_TM_FWD_IMPL="matmul", RTAP_TM_LEARN_ROWS="compact")
    procs = [subprocess.Popen([sys.executable, "-c", _LOWER], env=env, cwd=REPO,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
             for env in (clean, loud)]
    shas = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        shas.append(next(ln for ln in out.splitlines() if ln.startswith("SHA ")))
    assert shas[0] == shas[1]
    assert int(shas[0].split()[2]) > 10_000  # a whole step was lowered


@pytest.mark.parametrize("rows", ["narrow", "wide"])
def test_no_state_carries_a_forward_index(rows):
    """`init_state` builds none and a learning step adds none, in either
    form; asking for one is an error, not a silent no-op."""
    from rtap_tpu.ops.step import fused_step

    cfg = scaled_cluster_preset(32) if rows == "narrow" else form_cfg("wide", 16)
    assert tm_tpu.wide_rows(cfg.tm) == (rows == "wide")
    state = init_state(cfg, 3)
    assert not [k for k in state if k.startswith("fwd_")]
    assert init_state(cfg, 3, include_fwd=False).keys() == state.keys()
    with pytest.raises(ValueError, match="include_fwd"):
        init_state(cfg, 3, include_fwd=True)
    stepped, _ = fused_step(jax.device_put(state), np.float32([41.5]),
                            np.int32(1_700_000_000), cfg, True)
    assert stepped.keys() == state.keys()
