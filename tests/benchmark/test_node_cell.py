"""The cell `node-3-replay`, held on the CPU: the committed configuration is
`node_preset(3)` with nothing overridden and fills over a third of the chip,
`learn_cap` is the structural bound there and in the preset, the manifest
lists the cell where ISSUE 37 says (the shape-free lists, four `*.node`
metrics since ISSUE 41, no `sp_overlap_roofline`), and the cell cut to a tiny
stream count runs through the unedited harness: correct, and not correct
under its u8 control."""

import json
import os
import shutil

import pytest

from benchmark import kernel_bytes_dense as kbd
from benchmark.registry import REPO, Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark.test_nab_cell import (
    HEADS, NAB_METRICS, REPLAY_HEAD, SHAPE_FREE, hand_made_record)
from tests.benchmark.test_room_for_fields import OPS
from tests.benchmark.tiny import failed_numbers, run

CELL, CONFIG = "node-3-replay", "node-3"
SEED = 4_370_000_001  # beyond 2**31, like the driver's
NODE_METRICS = {"tm_roofline.node": "rtap.tm",
                "sp_learn_roofline.node": "rtap.sp.learn",
                "step_roofline.node": None,
                "tm_full_cells.node": None}


def node_config() -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def make_root(tmp_path, groups: int = 2, group_size: int = 4) -> str:
    """The committed benchmark under a temp root, the one configuration cut
    to a stream count the CPU holds; every width stays."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    path = os.path.join(root, "benchmark", "configs", CONFIG + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["layout"].update(groups=groups, group_size=group_size,
                         streams=groups * group_size)
    cfg["correct_sample_streams"] = 2
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_node"))


# ---- the committed files ----

def test_config_file_is_the_preset_with_nothing_overridden():
    from rtap_tpu.config import node_preset

    cfg = node_config()
    preset = node_preset(3)
    assert cfg["model"] == preset.to_dict()
    tm = cfg["model"]["tm"]
    assert tm["learn_cap"] == 320 == \
        tm["col_cap"] * tm["cells_per_column"] * tm["max_segments_per_cell"]
    assert cfg["model"]["n_fields"] == 3 and preset.input_size == 384
    assert cfg["reduced"] == [] and "learn_cap" in cfg["assumed"]
    assert "correct_ticks" not in cfg and "live_cadence_s" not in cfg
    # precision, control and sample as cluster-256 states them; of the
    # guarantees only `capacity` says more (structurally 0)
    with open(os.path.join(REPO, "benchmark", "configs", "cluster-256.json")) as f:
        cluster = json.load(f)
    for key in ("precision", "control", "correct_sample_streams"):
        assert cfg[key] == cluster[key], key
    assert set(cfg["guarantees"]) == set(cluster["guarantees"])
    assert {k for k, v in cluster["guarantees"].items()
            if cfg["guarantees"][k] != v} == {"capacity"}
    assert cfg["guarantees"]["capacity"].startswith(
        cluster["guarantees"]["capacity"])


def test_state_on_the_device_is_over_a_quarter_of_the_chip():
    cfg = node_config()
    layout = cfg["layout"]
    assert layout["streams"] == layout["groups"] * layout["group_size"] == 8192
    per_node = kbd.state_bytes_per_stream(cfg["model"])
    assert per_node == 760_871
    share = layout["streams"] * per_node / (16 * 2 ** 30)
    assert share >= 0.25 and share == pytest.approx(0.3628, abs=1e-4)


def manifest_holds(reg: Registry) -> None:
    """What this cell's test holds of a manifest (tests/benchmark/
    manifest_rules.py): the committed one, and the rehearsal's copy."""
    entry = rules.entry(reg.manifest["configs"], CONFIG)
    cfg = node_config()
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    rules.cell_entry(reg, CELL, CONFIG, "replay-full")
    rules.reports_at_least(reg, CELL, "end_to_end",
                           {"metrics_per_s", "setup_s", "peak_bytes_per_stream"})
    shared = SHAPE_FREE | {"host_gc_ms.replay"}  # the second since ISSUE 45
    layer = rules.reports_at_least(reg, CELL, "per_layer",
                                   shared | set(NODE_METRICS))
    # every shape-free scope and phase metric lists the cell right after the
    # cells accepted before it (a later cell may follow; none may drop out)
    for name in shared:
        rules.listed_after(
            layer[name]["workloads"],
            [*HEADS.get(name, REPLAY_HEAD), "nab-2048-replay"], CELL)
    for name in NODE_METRICS:  # its own: it stands first on each
        rules.listed_after(layer[name]["workloads"], [], CELL)
    rules.added_in_order(reg.manifest["per_layer"], NODE_METRICS,
                         after=SHAPE_FREE | set(NAB_METRICS))
    # the overlap's share reads over 100 % at this shape (the mask is staged
    # on chip: PERF.md s7), so the cell reports none
    assert not [n for n in layer if n.startswith("sp_overlap_roofline")]
    for m in layer.values():
        rules.agrees_with_definition(reg, m)
    for name, scope in NODE_METRICS.items():
        definition, _ = reg.layer_metric(name)
        assert definition.get("scope") == scope
        assert definition["reader"] == (
            "segment_capacity" if name == "tm_full_cells.node"
            else "dense_roofline")


def test_manifest_lists_the_cell_where_the_issue_says():
    manifest_holds(Registry())


def test_the_node_metrics_read_a_hand_made_trace_and_nothing_from_none():
    reg = Registry()
    record = hand_made_record(node_config(), OPS)
    model = record["config"]["model"]

    def read(name, rec=record):
        definition, reader = reg.layer_metric(name)
        return reader.read(rec, definition)

    def floor_ms(scope):
        return kbd.kernel_floor_seconds(scope, model, 1024, "TPU v5 lite") * 1e3

    # ns per 2-tick program -> ms per tick; the TM's share counts every
    # `rtap.tm.*` scope: learn, its rows, dendrite
    assert read("tm_roofline.node") == pytest.approx(
        100 * floor_ms("rtap.tm") / ((1400 + 200 + 1200) / 2 / 1e6))
    assert read("sp_learn_roofline.node") == pytest.approx(
        100 * floor_ms("rtap.sp.learn") / (500 / 2 / 1e6))
    assert read("step_roofline.node") == pytest.approx(
        100 * kbd.step_floor_seconds(model, 1024, "TPU v5 lite") * 1e3
        / (4000 / 2 / 1e6))
    assert read("tm_full_cells.node", {"tm_capacity": {"full_cells": 7}}) == 7
    # a program without the scopes or the counter (the parent of a later
    # PR's comparison): nothing to read, never 0 and never an error
    for name in NODE_METRICS:
        assert read(name, {"trace": None}) is None, name
    assert read("tm_full_cells.node", {"tm_capacity": {}}) is None


# ---- the cell through benchmark.run, at a tiny stream count ----

def test_tiny_cell_is_correct_and_feeds_three_fields_a_node(root):
    result, record = run(root, CELL, SEED, 0.5)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and record["groups_stepped"] == 2
    assert set(result["metrics"]) == {"metrics_per_s", "peak_bytes_per_stream",
                                      "setup_s"}
    assert record["sample"][0]["values"].shape[1:] == (3,)
    assert record["config"]["model"]["tm"]["learn_cap"] == 320
    definition, reader = Registry(root).layer_metric("tm_full_cells.node")
    assert reader.read(record, definition) == 0


def test_tiny_cell_under_its_u8_control_is_not_correct(root):
    control, _ = run(root, CELL, SEED, 0.5, control=True)
    assert not control["correct"]
    assert "perm_max_frac_diff" in failed_numbers(control)
