"""The door stays open: a multi-field deployment of the dense-pool family —
configuration, replay cell, per-layer rooflines — a further configuration
that states `live_cadence_s` with its live cell on a traffic file of its own,
and a further per-layer metric, added to a copy of the committed benchmark as
NEW files and APPENDED manifest entries (tests/benchmark/room.py), pass what
EVERY committed cell's test asks of the manifest (each file's
`manifest_holds`, found by name: tests/benchmark/manifest_rules.py), read
their metrics through the readers the benchmark has, and run through the
unedited harness. A `model_config`, `perf_opt` or `tracing` PR that brings
such entries therefore needs no edit to a file under BENCHMARK.json's
`paths` — and a cell's test that pins a last place fails here, in the PR
that writes it."""

import filecmp
import json
import os

import pytest

from benchmark import kernel_bytes_dense as kbd
from benchmark.registry import REPO, Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark import room, tiny
from tests.benchmark.test_nab_cell import (
    DEND, LEARN, ROWS, SPO, hand_made_record)
from tests.benchmark.test_registry import manifest_resolves_every_name

SPL = "jit(chunk_step)/while/body/closed_call/vmap(jit(sp_step))/rtap.sp.learn/select_n:"
SEED = 4_330_000_001  # beyond 2**31, like the driver's


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return room.make_root(tmp_path_factory.mktemp("room"))


def _files(top: str) -> set[str]:
    return {os.path.relpath(os.path.join(d, f), top)
            for d, _dirs, files in os.walk(top) for f in files
            if "__pycache__" not in d}


def test_the_cell_comes_as_new_files_and_appended_entries_only(root):
    before = _files(os.path.join(REPO, "benchmark"))
    after = _files(os.path.join(root, "benchmark"))
    assert after - before == {
        os.path.join("configs", room.CONFIG + ".json"),
        os.path.join("configs", room.LIVE_CONFIG + ".json"),
        os.path.join("traffic", room.LIVE_TRAFFIC + ".json"),
        *(os.path.join("layer_metrics", n + ".json")
          for n in [*room.ROOFLINES, room.METRIC])}
    assert not before - after
    for rel in sorted(before):  # every file the benchmark had, to the byte
        assert filecmp.cmp(os.path.join(REPO, "benchmark", rel),
                           os.path.join(root, "benchmark", rel),
                           shallow=False), rel
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        had = json.load(f)
    got = Registry(root).manifest
    assert set(got) == set(had)
    for key in ("command", "paths", "run_seconds"):
        assert got[key] == had[key]
    # taking the appended entries away again gives the committed manifest
    for key, names in (("configs", [room.CONFIG, room.LIVE_CONFIG]),
                       ("workloads", [room.CELL, room.LIVE_CELL]),
                       ("per_layer", [*room.ROOFLINES, room.METRIC])):
        n = len(had[key])
        assert [e["name"] for e in got[key][n:]] == names, key
    n = len(had["per_layer"])
    joined = {room.CELL: 0, room.LIVE_CELL: 0}
    for old, new in zip(had["end_to_end"] + had["per_layer"],
                        got["end_to_end"] + got["per_layer"][:n]):
        if "workloads" in old:
            k = len(old["workloads"])
            for cell in new["workloads"][k:]:
                joined[cell] += 1  # a KeyError: a cell the room did not add
            new = {**new, "workloads": new["workloads"][:k]}
        assert new == old
    # metrics_per_s and every shape-free list — the accepted replay cells'
    # own that hold the dense family's first cell; score_p50_ms and every
    # list the accepted live cell is on
    assert joined == {room.CELL: 1 + len(room.shape_free_lists(had)),
                      room.LIVE_CELL: len(room.live_lists(had))}
    assert len(room.live_lists(had)) >= 1 + 25  # the 25 of ISSUE 43


def test_the_copy_passes_every_committed_cells_manifest_function(root):
    reg = Registry(root)
    manifest_resolves_every_name(reg)
    held = rules.manifest_functions()
    assert set(held) >= {"test_nab_cell", "test_node_cell",
                         "test_node_live_cell", "test_host_spans",
                         "test_scoped_trace"}
    for holds in held.values():
        holds(reg)  # an AssertionError's traceback names the file that pins
    # the further replay cell is all that the NAB cell is, but for its names
    cfg = reg.cell(room.CELL)["config"]
    assert cfg["layout"]["streams"] == 6 * 1024 and "live_cadence_s" not in cfg
    assert kbd.state_bytes_per_stream(cfg["model"]) == 760_871
    layer = reg.metrics(room.CELL, "per_layer")
    assert {m["name"] for m in layer} == \
        {m["name"] for m in room.shape_free_lists(reg.manifest)} \
        | set(room.ROOFLINES)
    assert {m["name"] for m in reg.metrics(room.CELL, "end_to_end")} == \
        {"metrics_per_s", "setup_s", "peak_bytes_per_stream"}


def test_the_further_live_cell_is_on_every_live_list(root):
    reg = Registry(root)
    live = reg.cell(room.LIVE_CELL)
    cfg, mix = live["config"], live["traffic"]
    assert cfg["live_cadence_s"] == mix["cadence_s"] == 1.0
    assert (mix["kind"], mix["phase_spread_s"], mix["guard_s"],
            mix["trace_window_s"]) == ("live", 0.5, 0.25, 2.0)
    assert cfg["layout"]["streams"] == 131_072 and cfg["reduced"] == []
    assert {m["name"] for m in reg.metrics(room.LIVE_CELL, "end_to_end")} == \
        {"score_p50_ms", "setup_s", "peak_bytes_per_stream"}
    # all that the accepted live cell reports, and the further metric
    names = [m["name"] for m in reg.metrics(room.LIVE_CELL, "per_layer")]
    assert names == [m["name"] for m in
                     reg.metrics(room.LIVE_HEAD, "per_layer")]
    assert len(names) >= 25 + 1 and names.count(room.METRIC) == 1
    # the further metric is a data file on a reader the benchmark has: host
    # ms a tick under `rtap.state.relayout` (0 over a live tick since PR 44)
    definition, reader = reg.layer_metric(room.METRIC)
    tick, relayout = "rtap.loop.tick", "rtap.state.relayout"
    notes = [["bench_sync", 1_000, 1_000, {}],
             [tick, 10_000, 400_000, {}], [tick, 1_010_000, 400_000, {}],
             [relayout, 20_000, 30_000, {"leaves": 2}],
             [relayout, 1_020_000, 50_000, {"leaves": 2}]]
    record = {"trace": {"window_s": 0.01},
              "scoped_planes": {"/host:CPU": {"annotations": notes}}}
    assert reader.read(record, definition) == pytest.approx(0.04)
    assert reader.read({"trace": None}, definition) is None


#: one 2-tick program with the dense SP's learning in it
OPS = (("%f.1 = f32[8]{0} fusion(%a)", 0, 1400, LEARN),
       ("%s.2 = f32[8]{0} fusion(%a)", 1400, 200, ROWS),
       ("%f.3 = s32[8]{0} fusion(%a)", 1600, 1200, DEND),
       ("%c.4 = s32[8]{0} convolution(%a)", 2800, 300, SPO),
       ("%f.6 = u16[8]{0} fusion(%a)", 3100, 500, SPL),
       ("%copy.5 = f32[8]{0} copy(%p)", 3600, 400, ""))


def test_the_new_rooflines_read_through_the_dense_reader(root):
    reg = Registry(root)
    record = hand_made_record(reg.cell(room.CELL)["config"], OPS)
    model = record["config"]["model"]

    def read(name):
        definition, reader = reg.layer_metric(name)
        assert definition["reader"] == "dense_roofline"
        return reader.read(record, definition)

    # floor / (ns per 2-tick program / 2): the floors of 1,024 node_preset(3)
    # models a group-tick at 819 GB/s
    floors_ms = {s: kbd.kernel_floor_seconds(s, model, 1024, "TPU v5 lite") * 1e3
                 for s in kbd.KERNELS}
    assert floors_ms == pytest.approx(
        {"rtap.sp.overlap": 0.3705, "rtap.sp.learn": 0.6218,
         "rtap.tm": 0.6686}, abs=5e-5)
    step_ms = kbd.step_floor_seconds(model, 1024, "TPU v5 lite") * 1e3
    assert step_ms == pytest.approx(1.9026, abs=5e-5)
    for name, scope, ns in (
            ("sp_overlap_roofline.fields", "rtap.sp.overlap", 300),
            ("sp_learn_roofline.fields", "rtap.sp.learn", 500),
            ("tm_roofline.fields", "rtap.tm", 1400 + 200 + 1200)):
        assert read(name) == pytest.approx(
            100 * floors_ms[scope] / (ns / 2 / 1e6)), name
    assert read("step_roofline.fields") == pytest.approx(
        100 * step_ms / (4000 / 2 / 1e6))
    # nothing to read -> nothing, never 0
    for name in room.ROOFLINES:
        definition, reader = reg.layer_metric(name)
        assert reader.read({"trace": None}, definition) is None


def test_the_further_cell_runs_through_the_unedited_harness(tmp_path):
    small = room.make_root(tmp_path, groups=2, group_size=4,
                           correct_sample_streams=2)
    result, record = tiny.run(small, room.CELL, SEED, 0.5)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and record["groups_stepped"] == 2
    assert set(result["metrics"]) == {"metrics_per_s", "peak_bytes_per_stream",
                                      "setup_s"}
    assert record["sample"][0]["values"].shape[1:] == (3,)
    control, _ = tiny.run(small, room.CELL, SEED, 0.5, control=True)
    assert not control["correct"]
    assert "perm_max_frac_diff" in tiny.failed_numbers(control)


def test_the_further_live_cell_runs_through_the_unedited_harness(tmp_path):
    small = room.make_root(tmp_path, groups=2, group_size=4, live_groups=2,
                           live_group_size=8, live_mix=tiny.TINY_LIVE,
                           correct_sample_streams=2)
    result, record = tiny.run(small, room.LIVE_CELL, SEED + 1, 3.6)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 3 * 16 and result["failed"] == 0
    assert set(result["metrics"]) == {"score_p50_ms", "peak_bytes_per_stream",
                                      "setup_s"}
    assert record["config"]["name"] == room.LIVE_CONFIG
    assert record["loop_stats"]["missed_deadlines"] == 0
