"""Data-driven: a configuration, a traffic mix, a cell and a per-layer
metric added as NEW files (plus their BENCHMARK.json entries) are found by
name with no edit to the harness; a missing one fails loudly."""

import json
import os

import pytest

from benchmark import kernel_bytes_dense, roofline
from benchmark.registry import NotFound, Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark.tiny import make_root, run


@pytest.fixture()
def root(tmp_path):
    return make_root(tmp_path)


def _edit_manifest(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    fn(bm)
    with open(path, "w") as f:
        json.dump(bm, f)


def manifest_resolves_every_name(reg: Registry) -> None:
    """What every cell of a manifest has to satisfy (the committed one here;
    test_room_for_fields.py holds a copy with a further cell to the same)."""
    for w in reg.manifest["workloads"]:
        cell = reg.cell(w["name"])
        assert callable(cell["kind"].run)
        # sized by its family's byte table: sparse pools, or dense ones
        model = cell["config"]["model"]
        table = roofline if model["sp"]["sparse_pool"] else kernel_bytes_dense
        assert cell["config"]["layout"]["streams"] * \
            table.state_bytes_per_stream(model) >= 4.0 * 2 ** 30
        assert {m["name"] for m in reg.metrics(w["name"], "end_to_end")} >= \
            {"setup_s", "peak_bytes_per_stream"}
        layer = reg.metrics(w["name"], "per_layer")
        assert layer
        for m in layer:
            rules.agrees_with_definition(reg, m)
    for c in reg.manifest["configs"]:
        assert os.path.isfile(os.path.join(reg.root, c["file"]))


def test_committed_manifest_resolves_every_name():
    manifest_resolves_every_name(Registry())


def test_new_files_are_found_by_name(root):
    bdir = os.path.join(root, "benchmark")
    # a new traffic mix of an existing kind, a new cell, a new metric + reader
    with open(os.path.join(bdir, "traffic", "replay-full.json")) as f:
        mix = json.load(f)
    mix.update(name="replay-short", chunk_ticks=4)
    with open(os.path.join(bdir, "traffic", "replay-short.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bdir, "readers", "chunks.py"), "w") as f:
        f.write("def read(record, definition):\n"
                "    return record.get('n_chunks')\n")
    with open(os.path.join(bdir, "layer_metrics", "chunks_done.json"), "w") as f:
        json.dump({"name": "chunks_done", "unit": "chunks", "better": "higher",
                   "layer": "stream groups", "moves": "metrics_per_s",
                   "reader": "chunks"}, f)

    def add(bm):
        bm["workloads"].append({"name": "tiny-replay-short", "config": "tiny-256",
                                "traffic": "replay-short", "chips": 1, "why": "t"})
        bm["per_layer"].append({"name": "chunks_done", "unit": "chunks",
                                "better": "higher", "source": "program_counter",
                                "layer": "stream groups", "moves": "metrics_per_s",
                                "workloads": ["tiny-replay-short"]})
        for m in bm["end_to_end"]:
            if m["name"] == "metrics_per_s":
                m["workloads"].append("tiny-replay-short")

    _edit_manifest(root, add)
    reg = Registry(root)
    cell = reg.cell("tiny-replay-short")
    assert cell["traffic"]["chunk_ticks"] == 4
    assert [m["name"] for m in reg.metrics("tiny-replay-short", "per_layer")] \
        == ["chunks_done"]
    definition, reader = reg.layer_metric("chunks_done")
    assert reader.read({"n_chunks": 7}, definition) == 7
    # ... and the new cell runs, through the unedited harness
    result, record = run(root, "tiny-replay-short", 4_200_000_001, 0.5)
    assert result["correct"] and record["chunk_ticks"] == 4
    assert "metrics_per_s" in result["metrics"]


@pytest.mark.parametrize("what,match", [
    ("cell", "is not in BENCHMARK.json"), ("config", "configs/nope.json"),
    ("traffic", "traffic/nope.json"), ("kind", "traffic_kinds/nope.py"),
    ("metric", "layer_metrics/nope.json"), ("reader", "readers/nope.py")])
def test_missing_names_fail_loudly(root, what, match):
    bdir = os.path.join(root, "benchmark")
    if what == "config":
        _edit_manifest(root, lambda bm: bm["workloads"][0].update(config="nope"))
    elif what == "traffic":
        _edit_manifest(root, lambda bm: bm["workloads"][0].update(traffic="nope"))
    elif what == "kind":
        path = os.path.join(bdir, "traffic", "replay-full.json")
        with open(path) as f:
            mix = json.load(f)
        mix["kind"] = "nope"
        with open(path, "w") as f:
            json.dump(mix, f)
    elif what == "reader":
        path = os.path.join(bdir, "layer_metrics", "warm_compile_s.json")
        with open(path) as f:
            d = json.load(f)
        d["reader"] = "nope"
        with open(path, "w") as f:
            json.dump(d, f)
    reg = Registry(root)
    with pytest.raises(NotFound, match=match):
        if what == "cell":
            reg.cell("nope")
        elif what == "metric":
            reg.layer_metric("nope")
        elif what == "reader":
            reg.layer_metric("warm_compile_s")
        else:
            reg.cell("tiny-replay")


def test_no_manifest_no_run(tmp_path):
    with pytest.raises(NotFound, match="no BENCHMARK.json"):
        Registry(str(tmp_path))
