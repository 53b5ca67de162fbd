"""The door stays open: a multi-field deployment of the dense-pool family —
configuration, replay cell, per-layer rooflines — added to a copy of the
committed benchmark as NEW files and APPENDED manifest entries
(tests/benchmark/room.py) passes what the manifest's own tests ask of every
committed cell, reads its rooflines through the reader the benchmark has, and
runs through the unedited harness. A `model_config` PR that brings such a
cell therefore needs no edit to a file under BENCHMARK.json's `paths`."""

import filecmp
import json
import os

import pytest

from benchmark import kernel_bytes_dense as kbd
from benchmark.registry import REPO, Registry
from tests.benchmark import room, tiny
from tests.benchmark.test_nab_cell import (
    DEND, LEARN, ROWS, SPO, cell_resolves_and_fills_a_quarter_of_the_chip,
    hand_made_record)
from tests.benchmark.test_registry import manifest_resolves_every_name
from tests.benchmark.test_scoped_trace import (
    metric_files_resolve_and_name_their_cells)

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
        *(os.path.join("layer_metrics", n + ".json") for n in room.ROOFLINES)}
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
    assert got["configs"][:-1] == had["configs"]
    assert got["workloads"][:-1] == had["workloads"]
    assert got["workloads"][-1]["name"] == room.CELL
    n = len(had["per_layer"])
    assert [m["name"] for m in got["per_layer"][n:]] == list(room.ROOFLINES)
    joined = 0
    for old, new in zip(had["end_to_end"] + had["per_layer"],
                        got["end_to_end"] + got["per_layer"][:n]):
        if room.CELL in new.get("workloads", ()):
            assert new["workloads"][-1] == room.CELL
            new = {**new, "workloads": new["workloads"][:-1]}
            joined += 1
        assert new == old
    # metrics_per_s and every shape-free list: the accepted replay cells'
    # own that hold the dense family's first cell
    assert joined == 1 + len(room.shape_free_lists(had))


def test_the_copy_passes_the_manifests_own_tests(root):
    reg = Registry(root)
    manifest_resolves_every_name(reg)
    cell_resolves_and_fills_a_quarter_of_the_chip(reg)
    metric_files_resolve_and_name_their_cells(reg)
    # ... and the further cell is all that the NAB cell is, but for its names
    cfg = reg.cell(room.CELL)["config"]
    assert cfg["layout"]["streams"] == 6 * 1024 and "live_cadence_s" not in cfg
    assert kbd.state_bytes_per_stream(cfg["model"]) == 760_871
    layer = reg.metrics(room.CELL, "per_layer")
    assert {m["name"] for m in layer} == \
        {m["name"] for m in room.shape_free_lists(reg.manifest)} \
        | set(room.ROOFLINES)
    assert {m["name"] for m in reg.metrics(room.CELL, "end_to_end")} == \
        {"metrics_per_s", "setup_s", "peak_bytes_per_stream"}


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
