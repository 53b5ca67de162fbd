"""The cell `nab-2048-replay`, held on the CPU at a tiny size: its twin runs
through the unedited harness and the `replay` kind and is correct, the u16
control is not, the dense family's byte table equals what init_state
allocates, and the readers the cell brings read a hand-made trace exactly.
(What the committed manifest asserts of every other cell,
test_registry.py::test_committed_manifest_resolves_every_name, is asserted
here of this one with the byte table that covers its family.)"""

import json
import os

import numpy as np
import pytest

from benchmark import kernel_bytes_dense as kbd
from benchmark.registry import Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark.tiny import failed_numbers
from tests.benchmark.tiny_nab import CELL, CONFIG, REPO, make_root, run

CONFIGS = os.path.join(REPO, "benchmark", "configs")
SEED = 4_270_000_001  # beyond 2**31, like the driver's


#: one name for one measurement across the shape range: what the step's
#: scopes and a group's phases took, and the outside-timed twins (PERF.md s3).
#: A later PR's shape-free metric joins these by listing the cell; none of
#: these may drop it.
SHAPE_FREE = {
    "encode_ms.replay", "sp_overlap_ms.replay", "sp_inhibit_ms.replay",
    "sp_learn_ms.replay", "tm_activate_ms.replay", "tm_learn_ms.replay",
    "tm_dendrite_ms.replay", "unscoped_ms.replay",
    "group_stage_ms.replay", "group_enqueue_ms.replay",
    "group_fetch_ms.replay", "group_likelihood_ms.replay",
    "warm_compile_s", "group_host_ms.replay", "step_device_ms.replay",
    "device_idle_share.replay"}
#: the cell's own, in the order they were added: the wide rows' sub-scope,
#: the dense byte table's shares, the capacity counter
NAB_METRICS = ("tm_learn_rows_ms.nab", "step_roofline.nab",
               "sp_overlap_roofline.nab", "tm_roofline.nab",
               "tm_full_cells.nab")


def nab_config() -> dict:
    with open(os.path.join(CONFIGS, CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_nab"))


# ---- the twin through benchmark.run ----

def test_tiny_twin_is_correct_and_reports_the_cells_metrics(root):
    result, record = run(root, SEED, 1.5)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and record["groups_stepped"] == 1
    assert record["n_chunks"] >= 2  # two chunks of the one group in flight
    assert set(result["metrics"]) == {"metrics_per_s", "peak_bytes_per_stream",
                                      "setup_s"}
    # first, last and one drawn from the seed (of 3 streams: 2 or 3 distinct)
    assert {s["stream"] for s in record["sample"]} >= {0, 2}
    # the family, as the program ran it
    from rtap_tpu.ops import tm_tpu
    from benchmark import program
    cfg = program.model_config(record["config"])
    assert not cfg.sp.sparse_pool and cfg.date.time_of_day_width == 21
    assert cfg.sp.perm_bits == cfg.tm.perm_bits == 0
    assert cfg.tm.cells_per_column == 32 and tm_tpu.wide_rows(cfg.tm)
    assert record["sample"][0]["syn_perm"].dtype == np.float32


def test_u16_control_is_not_correct(root):
    control, _ = run(root, SEED, 1.5, control=True)
    assert not control["correct"]
    assert "perm_max_frac_diff" in failed_numbers(control)


def test_a_step_that_learns_nothing_is_not_correct(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    from rtap_tpu.service.registry import StreamGroup

    inner = StreamGroup.dispatch_chunk

    def dispatch_chunk(self, values, ts, learn=True):
        kept = jax.tree.map(jnp.copy, self.state)
        handle = inner(self, values, ts, learn=learn)
        self.state = kept
        return handle

    monkeypatch.setattr(StreamGroup, "dispatch_chunk", dispatch_chunk)
    result, _ = run(root, SEED + 1, 1.0)
    assert not result["correct"]
    assert "perm_max_frac_diff" in failed_numbers(result)


# ---- the committed files ----

#: the cells the shape-free lists held when this cell joined them
REPLAY_HEAD = ["cluster-256-replay", "cluster-32-replay"]
HEADS = {"warm_compile_s": [*REPLAY_HEAD, "cluster-256-live"]}


def cell_resolves_and_fills_a_quarter_of_the_chip(reg: Registry) -> None:
    """What this cell's test holds of a manifest (tests/benchmark/
    manifest_rules.py): the committed one, and the rehearsal's copy."""
    rules.cell_entry(reg, CELL, CONFIG, "replay-full")
    cell = reg.cell(CELL)
    assert callable(cell["kind"].run) and cell["traffic"]["name"] == "replay-full"
    cfg = cell["config"]
    assert cfg["layout"]["streams"] * kbd.state_bytes_per_stream(cfg["model"]) \
        >= 4.0 * 2 ** 30
    rules.reports_at_least(reg, CELL, "end_to_end",
                           {"metrics_per_s", "setup_s", "peak_bytes_per_stream"})
    layer = rules.reports_at_least(reg, CELL, "per_layer",
                                   SHAPE_FREE | set(NAB_METRICS))
    for m in layer.values():
        rules.agrees_with_definition(reg, m)
        assert m["moves"] in ("metrics_per_s", "setup_s")
    # one name for one measurement: the shape-free scope and phase metrics
    # are the accepted cells' own, with this cell on their lists right after
    # the accepted heads (a later cell may follow it: tests/benchmark/room.py)
    for name in SHAPE_FREE:
        rules.listed_after(layer[name]["workloads"],
                           HEADS.get(name, REPLAY_HEAD), CELL)
    for name in NAB_METRICS:  # its own: it stands first on each
        rules.listed_after(layer[name]["workloads"], [], CELL)
    rules.added_in_order(reg.manifest["per_layer"], NAB_METRICS,
                         after=SHAPE_FREE)
    assert not [m for m in reg.manifest["per_layer"]
                if m["name"].startswith(("tm_learn_roofline.",
                                         "tm_dendrite_roofline."))]
    entry = rules.entry(reg.manifest["configs"], CONFIG)
    assert entry["reduced"] == cfg["reduced"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


manifest_holds = cell_resolves_and_fills_a_quarter_of_the_chip


def test_committed_cell_resolves_and_fills_a_quarter_of_the_chip():
    cell_resolves_and_fills_a_quarter_of_the_chip(Registry())


def test_config_file_is_the_preset_with_nothing_overridden():
    from rtap_tpu.config import nab_preset

    cfg = nab_config()
    assert cfg["model"] == nab_preset(0.0, 100.0).to_dict()
    assert cfg["control"]["model_overrides"] == {"sp": {"perm_bits": 16},
                                                 "tm": {"perm_bits": 16}}
    # the cluster configurations' guarantees, `scores` and `state` restated
    # for the ticks this configuration has `correct` follow
    with open(os.path.join(CONFIGS, "cluster-256.json")) as f:
        cluster = json.load(f)["guarantees"]
    assert set(cfg["guarantees"]) == set(cluster)
    assert {k for k in cluster if cfg["guarantees"][k] != cluster[k]} == \
        {"scores", "state"}
    assert 0 < cfg["precision"]["perm_tolerance"] < 0.5 / 65535  # under half a u16 quantum


# ---- bytes from shapes ----

def test_dense_byte_table_equals_init_state_leaf_by_leaf():
    from rtap_tpu.config import ModelConfig
    from rtap_tpu.models.state import init_state

    model = nab_config()["model"]
    state = init_state(ModelConfig.from_dict(model), 0, include_fwd=False)
    leaves = kbd.leaf_bytes(model)
    assert set(kbd.STATE_LEAVES) == set(state)
    for k in kbd.STATE_LEAVES:
        assert leaves[k] == np.asarray(state[k]).nbytes, k
    assert kbd.state_bytes_per_stream(model) == 281_628_693
    for scope, (read, written) in kbd.KERNELS.items():
        assert set(read) | set(written) <= set(leaves), scope
        assert kbd.kernel_bytes_per_stream(scope, model) < \
            2 * kbd.state_bytes_per_stream(model)
    # the dense overlap: mask + permanences + SDR in, overlaps out
    assert kbd.kernel_bytes_per_stream("rtap.sp.overlap", model) == \
        2048 * 454 + 2048 * 454 * 4 + 454 + 2048 * 4
    assert kbd.step_floor_seconds(model, 17, "TPU v5 lite") == \
        pytest.approx(2 * 281_628_693 * 17 / 819e9)
    with pytest.raises(KeyError, match="no byte count"):
        kbd.kernel_bytes_per_stream("rtap.encode", model)


@pytest.mark.parametrize("name", ["cluster-256", "cluster-32"])
def test_dense_byte_table_refuses_the_sparse_family(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        model = json.load(f)["model"]
    with pytest.raises(ValueError, match="dense-pool"):
        kbd.leaf_bytes(model)


# ---- the readers, on a hand-made trace ----

LEARN = "jit(chunk_step)/while/body/closed_call/vmap(jit(tm_step))/rtap.tm.learn/select_n:"
ROWS = "jit(chunk_step)/while/body/closed_call/vmap(jit(tm_step))/rtap.tm.learn/rtap.tm.learn.rows/scatter:"
DEND = "jit(chunk_step)/while/body/closed_call/vmap(jit(tm_step))/rtap.tm.dendrite/reduce_sum:"
SPO = "jit(chunk_step)/while/body/closed_call/vmap(jit(sp_step))/rtap.sp.overlap/dot_general:"


#: one 2-tick program's ops: (hlo text, start within the program, ns, op_name)
NAB_OPS = (("%f.1 = f32[8]{0} fusion(%a)", 0, 1600, LEARN),
           ("%s.2 = f32[8]{0} fusion(%a)", 1600, 400, ROWS),
           ("%f.3 = s32[8]{0} fusion(%a)", 2000, 1500, DEND),
           ("%c.4 = s32[8]{0} convolution(%a)", 3500, 100, SPO),
           ("%copy.5 = f32[8]{0} copy(%p)", 3600, 400, ""))


def hand_made_record(config: dict | None = None, ops=NAB_OPS) -> dict:
    # a program clipped by the tracer's start, then two whole 2-tick programs
    planes = {"/device:TPU:0": {
        "XLA Modules": [["jit_chunk_step(1)", 100, 100],
                        ["jit_chunk_step(1)", 1000, 4000],
                        ["jit_chunk_step(1)", 6000, 4000]],
        "XLA Ops": [["%f.1 = f32[8]{0} fusion(%a)", 100, 100, LEARN]] + [
            [text, t0 + at, ns, name] for t0 in (1000, 6000)
            for text, at, ns, name in ops]},
        "/host:CPU": {"annotations": [["bench_sync", 50, 5, {}]]}}
    return {"trace": {"window_s": 1.0}, "scoped_planes": planes,
            "chunk_ticks": 2, "device_kind": "TPU v5 lite",
            "config": config or nab_config()}


def test_new_readers_on_a_hand_made_trace():
    reg = Registry()
    record = hand_made_record()
    model = record["config"]["model"]

    def read(name):
        definition, reader = reg.layer_metric(name)
        return reader.read(record, definition)

    # ns per 2-tick program -> ms per tick
    assert read("tm_learn_ms.replay") == pytest.approx(1600 / 2 / 1e6)
    assert read("tm_learn_rows_ms.nab") == pytest.approx(400 / 2 / 1e6)
    assert read("tm_dendrite_ms.replay") == pytest.approx(1500 / 2 / 1e6)
    assert read("unscoped_ms.replay") == pytest.approx(400 / 2 / 1e6)
    assert read("encode_ms.replay") == 0.0
    # a kernel's share counts its sub-scopes' time — the TM's, every
    # `rtap.tm.*` scope's (learn, its rows, dendrite); the step's, every scope's
    floor = kbd.kernel_floor_seconds("rtap.tm", model, 17, "TPU v5 lite")
    assert read("tm_roofline.nab") == pytest.approx(
        100 * floor / ((1600 + 400 + 1500) / 2 / 1e9))
    floor = kbd.kernel_floor_seconds("rtap.sp.overlap", model, 17, "TPU v5 lite")
    assert read("sp_overlap_roofline.nab") == pytest.approx(
        100 * floor / (100 / 2 / 1e9))
    assert read("step_roofline.nab") == pytest.approx(
        100 * kbd.step_floor_seconds(model, 17, "TPU v5 lite") / (4000 / 2 / 1e9))


def test_new_readers_read_nothing_where_there_is_nothing():
    reg = Registry()
    bare = hand_made_record()
    for ev in bare["scoped_planes"]["/device:TPU:0"]["XLA Ops"]:
        ev[3] = ev[3].replace("rtap.", "")  # a program before the scopes
    for name in ("step_roofline.nab", "tm_roofline.nab",
                 "tm_learn_rows_ms.nab", "tm_learn_ms.replay"):
        definition, reader = reg.layer_metric(name)
        assert reader.read({"trace": None}, definition) is None
        assert reader.read(bare, definition) is None, name
    definition, reader = reg.layer_metric("tm_full_cells.nab")
    assert reader.read({"sample": []}, definition) is None
    assert reader.read({"tm_capacity": {}}, definition) is None


def test_full_cells_counter_and_its_reader():
    from rtap_tpu.service.registry import segment_capacity

    C, K, S = 3, 4, 2
    in_use = np.zeros((2, C, K, S), bool)
    in_use[0, 1] = True          # a column whose every cell is full
    in_use[1, 2, 3] = True       # one more full cell
    in_use[1, 0, 0, 1] = True    # a cell half in use
    assert segment_capacity(in_use) == {
        "full_cells": K + 1, "full_columns": 1, "max_segments_on_a_cell": S}
    assert segment_capacity(np.zeros((1, C, K, S), bool)) == {
        "full_cells": 0, "full_columns": 0, "max_segments_on_a_cell": 0}
    # the replay kind sums every group's count into the record; the reader
    # reads that, never the sampled rows (which are a mid-run tick's)
    class Group:
        def __init__(self, rows):
            self.rows = rows

        def capacity_stats(self):
            return segment_capacity(self.rows)

    from benchmark import program
    total = program.capacity_total([Group(in_use[:1]), Group(in_use[1:])])
    assert total == {"full_cells": K + 1, "full_columns": 1,
                     "max_segments_on_a_cell": S}
    assert program.capacity_total([object()]) == {}
    definition, reader = Registry().layer_metric("tm_full_cells.nab")
    assert reader.read({"tm_capacity": total, "sample": []}, definition) == K + 1


def test_full_cells_reads_zero_after_a_short_run(root):
    _, record = run(root, SEED + 2, 0.5)
    definition, reader = Registry().layer_metric("tm_full_cells.nab")
    assert reader.read(record, definition) == 0
