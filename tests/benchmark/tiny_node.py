"""A three-field deployment for the CPU, added to the tiny rig's root as NEW
files — a configuration file and one manifest entry, the way a later
`model_config` PR brings its deployment: `node_preset(3)` (one model a node,
three metrics fused into one SDR, dense SP pool, 4 segments a cell) at
2 groups x 4 nodes, with the lower-precision control the cluster
configurations state (u8 quanta under its u16)."""

from __future__ import annotations

import json
import os

from tests.benchmark import tiny

CELL, CONFIG = "tiny-node-replay", "tiny-node"


def make_root(tmp_path, groups: int = 2, group_size: int = 4, **keys) -> str:
    """tiny.make_root's checkout plus the cell; `keys` are further keys of
    the configuration file (`correct_ticks`)."""
    from rtap_tpu.config import node_preset

    root = tiny.make_root(tmp_path)
    cfg_dir = os.path.join(root, "benchmark", "configs")
    with open(os.path.join(cfg_dir, "cluster-256.json")) as f:
        cfg = json.load(f)  # its guarantees, precision and control
    cfg.update(name=CONFIG, model=node_preset(3).to_dict(),
               correct_sample_streams=2, **keys)
    cfg["layout"].update(groups=groups, group_size=group_size,
                         streams=groups * group_size)
    with open(os.path.join(cfg_dir, CONFIG + ".json"), "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append({"name": CONFIG, "source": "tests", "reduced": [],
                          "file": f"benchmark/configs/{CONFIG}.json",
                          "why": "t"})
    bm["workloads"].append({"name": CELL, "config": CONFIG,
                            "traffic": "replay-full", "chips": 1, "why": "t"})
    for m in bm["end_to_end"]:
        if m["name"] == "metrics_per_s":
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bm, f)
    return root


def run(root: str, seed: int, seconds: float, **kw):
    return tiny.run(root, CELL, seed, seconds, **kw)
