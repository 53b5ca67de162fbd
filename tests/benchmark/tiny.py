"""A tiny copy of the benchmark for the CPU: the real harness, kinds,
readers and generator under a temp root whose BENCHMARK.json names cells of
2 groups x 8 streams — added as data files, the way a later PR adds a cell."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: two send batches a slot, at +0.15 and +0.30 s; the margins are wide because
#: the driver runs these tests six workers at a time
TINY_LIVE = {"cadence_s": 1.0, "phase_spread_s": 0.3, "guard_s": 0.35,
             "send_quantum_s": 0.15, "trace_window_s": 1.2}


def make_root(tmp_path, groups: int = 2, group_size: int = 8) -> str:
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cfg_dir = os.path.join(root, "benchmark", "configs")
    with open(os.path.join(cfg_dir, "cluster-256.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-256"
    cfg["layout"].update(groups=groups, group_size=group_size,
                         streams=groups * group_size)
    cfg["correct_sample_streams"] = 6
    cfg["live_cadence_s"] = TINY_LIVE["cadence_s"]
    with open(os.path.join(cfg_dir, "tiny-256.json"), "w") as f:
        json.dump(cfg, f)
    tr_dir = os.path.join(root, "benchmark", "traffic")
    with open(os.path.join(tr_dir, "live-5s.json")) as f:
        live = json.load(f)
    for name, hold in (("live-tiny", True), ("live-tiny-unheld", False)):
        live.update(name=name, hold_until_snapshot=hold, **TINY_LIVE)
        with open(os.path.join(tr_dir, name + ".json"), "w") as f:
            json.dump(live, f)
    rename = {"cluster-256-replay": ["tiny-replay"],
              "cluster-32-replay": ["tiny-replay"],
              "cluster-256-live": ["tiny-live", "tiny-live-unheld"]}
    bm["configs"] = [{"name": "tiny-256", "source": "tests", "reduced": [],
                      "file": "benchmark/configs/tiny-256.json", "why": "t"}]
    bm["workloads"] = [
        {"name": "tiny-replay", "config": "tiny-256",
         "traffic": "replay-full", "chips": 1, "why": "t"},
        {"name": "tiny-live", "config": "tiny-256", "traffic": "live-tiny",
         "chips": 1, "why": "t"},
        {"name": "tiny-live-unheld", "config": "tiny-256",
         "traffic": "live-tiny-unheld", "chips": 1, "why": "t"}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            # a cell the rig has no twin of keeps its metrics out of the way
            m["workloads"] = sorted({t for w in m["workloads"]
                                     for t in rename.get(w, ())})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def failed_numbers(result) -> set:
    """Names of the compared numbers that a run's result line failed."""
    return {n["name"] for n in result["compared"] if not n["ok"]}


def run(root: str, workload: str, seed: int, seconds: float, **kw):
    """run_cell without the look for a chip -> (result, record)."""
    from benchmark.run import run_cell

    rc, result, record = run_cell(workload, seed, seconds, False, root=root,
                                  allow_cpu=True, **kw)
    assert rc == 0
    return result, record
