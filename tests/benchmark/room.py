"""The room for a further multi-field cell, rehearsed: a copy of the COMMITTED
benchmark (BENCHMARK.json and benchmark/, nothing cut to size) to which a
configuration, its replay cell and its per-layer roofline metrics are added
the way a `model_config` PR must add them — new files and appended manifest
entries only, no edit to a file the benchmark has.

The deployment is `node_preset(3)` (one model a node, three metrics fused
into one 384-bit SDR, dense SP pool, u16 permanences, 4 segments a cell) at
6 groups x 1,024 nodes = 4.35 GiB of state, with `cluster-256`'s precision,
control and guarantees. The names are the rehearsal's own, so that it stays
true once a real cell of this family is committed: it then guards that the
harness takes a further one. tests/benchmark/test_room_for_fields.py holds
the copy to what the manifest's own tests ask of every committed cell."""

from __future__ import annotations

import json
import os
import shutil

from benchmark.registry import REPO

CELL, CONFIG = "rehearsal-fields-replay", "rehearsal-fields"
REPLAY_HEAD = ["cluster-256-replay", "cluster-32-replay"]
DENSE_CELL = "nab-2048-replay"

#: the dense family's rooflines (reader `dense_roofline`, written by PR 27):
#: metric -> the definition's `what` and `scope`. Data files only.
ROOFLINES = {
    "sp_overlap_roofline.fields": ("kernel", "rtap.sp.overlap"),
    "sp_learn_roofline.fields": ("kernel", "rtap.sp.learn"),
    "tm_roofline.fields": ("kernel", "rtap.tm"),
    "step_roofline.fields": ("step", None),
}


def shape_free_lists(manifest: dict) -> list[dict]:
    """The per-layer metrics one name serves across the shape range — the
    accepted replay cells' own, which already hold the dense family's first
    cell: a further cell of that family joins these lists."""
    return [m for m in manifest["per_layer"]
            if m.get("workloads", [])[:2] == REPLAY_HEAD
            and DENSE_CELL in m["workloads"]]


def make_root(tmp_path, groups: int = 6, group_size: int = 1024, **keys) -> str:
    """-> the root of the copy. `keys` are further keys of the configuration
    file (`correct_ticks`, `correct_sample_streams`)."""
    from rtap_tpu.config import node_preset

    root = os.path.join(str(tmp_path), "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", "cluster-256.json")) as f:
        accepted = json.load(f)
    cfg = {
        "name": CONFIG,
        "source": "rtap_tpu/config.py:node_preset(3); BASELINE.json "
                  "configs[3]; SURVEY.md s6 config 4",
        "reduced": [],
        "layout": {**accepted["layout"], "groups": groups,
                   "group_size": group_size, "streams": groups * group_size},
        **{k: accepted[k] for k in ("precision", "control", "guarantees",
                                    "correct_sample_streams")},
        "model": node_preset(3).to_dict(),
        **keys,
    }
    with open(os.path.join(bdir, "configs", CONFIG + ".json"), "w") as f:
        json.dump(cfg, f, indent=1)
    for name, (what, scope) in ROOFLINES.items():
        definition = {"name": name, "unit": "%", "better": "higher",
                      "layer": "kernels", "moves": "metrics_per_s",
                      "reader": "dense_roofline", "what": what,
                      "module": "jit_chunk_step"}
        if scope:
            definition["scope"] = scope
        with open(os.path.join(bdir, "layer_metrics", name + ".json"), "w") as f:
            json.dump(definition, f, indent=1)

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({
        "name": CONFIG, "source": cfg["source"], "reduced": [],
        "file": f"benchmark/configs/{CONFIG}.json",
        "why": "one model a node, three metrics fused into one SDR (dense SP "
               "pool, u16 permanences, 4 segments a cell, 760,871 B/stream): "
               f"{groups} groups x {group_size}"})
    bm["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "replay-full", "chips": 1,
        "why": "a record of three fields a model replayed at full rate: the "
               "multi-field encoder, the dense SP branch in the u16 domain "
               "at 1,024 streams a group, the TM at twice cluster-256's pool"})
    for m in bm["end_to_end"]:
        if m["name"] == "metrics_per_s":
            m["workloads"].append(CELL)
    for m in shape_free_lists(bm):
        m["workloads"].append(CELL)
    bm["per_layer"] += [
        {"name": name, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "metrics_per_s", "workloads": [CELL]} for name in ROOFLINES]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f, indent=1)
    return root

