"""The rule for what a cell's test may assert of BENCHMARK.json
(tests/benchmark/manifest_rules.py) still bites: on a copy of the committed
manifest, a cell taken off a list, accepted heads swapped, a cell put before
them, an entry edited, a metric removed or moved, a `moves` that disagrees
with its definition — each fails some committed cell's `manifest_holds`,
while appended entries pass (test_room_for_fields.py). And the first cell the
room is for comes as data files: `cluster-32`, which states the documented
1 s cadence since ISSUE 45, takes a live mix and is correct, and not correct
under its u8 control."""

import copy
import json
import os

import pytest

from benchmark.registry import REPO, Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark import room, tiny

SEED = 4_450_000_001  # beyond 2**31, like the driver's


def per_layer(bm, name):
    return rules.entry(bm["per_layer"], name)


def off_a_shared_list(bm):
    per_layer(bm, "loop_dispatch_ms")["workloads"].remove("node-3-live")


def off_a_replay_list(bm):
    per_layer(bm, "host_gc_ms.replay")["workloads"].remove("node-3-replay")


def heads_swapped(bm):
    w = per_layer(bm, "tm_learn_ms.replay")["workloads"]
    w[0], w[1] = w[1], w[0]


def live_heads_swapped(bm):
    w = per_layer(bm, "warm_compile_s")["workloads"]
    i, j = w.index("nab-2048-replay"), w.index("node-3-replay")
    w[i], w[j] = w[j], w[i]


def put_before_the_heads(bm):
    per_layer(bm, "group_fetch_ms.live")["workloads"].insert(0, "later-live")


def put_between_heads_and_cell(bm):
    w = per_layer(bm, "tm_dendrite_ms.live")["workloads"]
    w.insert(w.index("node-3-live"), "later-live")


def cell_twice(bm):
    per_layer(bm, "sp_learn_ms.replay")["workloads"].append("nab-2048-replay")


def traffic_edited(bm):
    rules.entry(bm["workloads"], "node-3-replay")["traffic"] = "replay-short"


def config_entry_edited(bm):
    rules.entry(bm["configs"], "node-3-served")["source"] += " (edited)"


def metric_removed(bm):
    bm["per_layer"].remove(per_layer(bm, "tm_full_cells.node"))


def own_metric_moved_up(bm):
    m = per_layer(bm, "tm_roofline.node.live")
    bm["per_layer"].remove(m)
    bm["per_layer"].insert(0, m)


def moves_against_its_definition(bm):
    per_layer(bm, "ingest_feed_ms")["moves"] = "setup_s"


def unit_against_its_definition(bm):
    per_layer(bm, "tm_learn_rows_ms.nab")["unit"] = "us"


MUTATIONS = [off_a_shared_list, off_a_replay_list, heads_swapped,
             live_heads_swapped, put_before_the_heads,
             put_between_heads_and_cell, cell_twice, traffic_edited,
             config_entry_edited, metric_removed, own_metric_moved_up,
             moves_against_its_definition, unit_against_its_definition]


@pytest.mark.parametrize("mutate", MUTATIONS, ids=lambda f: f.__name__)
def test_an_edit_to_what_is_accepted_fails_some_cells_manifest_function(mutate):
    reg = Registry()
    committed = copy.deepcopy(reg.manifest)
    mutate(reg.manifest)
    assert reg.manifest != committed
    with pytest.raises(AssertionError):
        rules.committed_cells_hold(reg)


def test_the_committed_manifest_holds_and_appending_keeps_it_so():
    reg = Registry()
    rules.committed_cells_hold(reg)
    # a later cell after the accepted ones, a later metric after theirs
    for m in reg.manifest["per_layer"]:
        m["workloads"].append("later-cell")
    reg.manifest["per_layer"].append(
        {**per_layer(reg.manifest, "loop_emit_ms"), "name": "later_ms",
         "workloads": ["cluster-256-live", "node-3-live", "later-cell"]})
    reg.manifest["workloads"].append(
        {**rules.entry(reg.manifest["workloads"], "node-3-live"),
         "name": "later-cell"})
    reg.layer_metric = lambda name, inner=reg.layer_metric: inner(
        "loop_emit_ms" if name == "later_ms" else name)
    rules.committed_cells_hold(reg)


def test_the_rule_helpers_say_what_they_hold():
    rules.listed_after(["a", "b", "c", "later"], ["a", "b"], "c")
    for workloads in (["b", "a", "c"], ["a", "b"], ["a", "b", "x", "c"],
                      ["x", "a", "b", "c"], ["a", "b", "c", "c"]):
        with pytest.raises(AssertionError):
            rules.listed_after(workloads, ["a", "b"], "c")
    names = [{"name": n} for n in "abcde"]
    rules.added_in_order(names, ["c", "e"], after={"a", "b"})
    for own, after in ((["e", "c"], ()), (["c", "e"], {"d"}),
                       (["c", "z"], ()), (["c"], {"z"})):
        with pytest.raises(AssertionError):
            rules.added_in_order(names, own, after=after)
    with pytest.raises(AssertionError):
        rules.entry(names + [{"name": "a"}], "a")
    with pytest.raises(AssertionError):
        rules.entry(names, "z")


# ---- cluster-32 takes a live mix, as data files ----

@pytest.fixture(scope="module")
def c32_root(tmp_path_factory):
    return room.make_cluster_32_live_root(tmp_path_factory.mktemp("c32_live"))


def test_cluster_32_states_the_documented_cadence_and_replay_ignores_it():
    with open(os.path.join(REPO, "benchmark", "configs", "cluster-32.json")) as f:
        cfg = json.load(f)
    assert cfg["live_cadence_s"] == 1.0 and cfg["reduced"] == []
    entry = rules.entry(Registry().manifest["configs"], "cluster-32")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    with open(os.path.join(REPO, "benchmark", "traffic_kinds", "replay.py")) as f:
        assert "live_cadence_s" not in f.read()


def test_cluster_32_live_is_correct_at_a_tiny_size(c32_root):
    reg = Registry(c32_root)
    cell = reg.cell(room.C32_CELL)
    assert cell["config"]["name"] == "cluster-32"
    assert cell["config"]["live_cadence_s"] == cell["traffic"]["cadence_s"]
    assert cell["traffic"]["kind"] == "live"
    result, record = tiny.run(c32_root, room.C32_CELL, SEED, 4.6)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 4 * 16 and result["failed"] == 0
    assert set(result["metrics"]) == {"score_p50_ms", "peak_bytes_per_stream",
                                      "setup_s"}
    assert record["config"]["model"]["sp"]["columns"] == 32
    assert record["loop_stats"]["missed_deadlines"] == 0


def test_cluster_32_live_under_its_u8_control_is_not_correct(c32_root):
    control, _ = tiny.run(c32_root, room.C32_CELL, SEED, 4.6, control=True)
    assert not control["correct"] and control["failed"] == 0
    assert "perm_max_frac_diff" in tiny.failed_numbers(control)
