"""The room for what a later PR brings, rehearsed: a copy of the COMMITTED
benchmark (BENCHMARK.json and benchmark/, nothing cut to size) to which a
configuration with its replay cell and per-layer roofline metrics, a further
configuration that states `live_cadence_s` with its live cell on a traffic
file of its own, and one further per-layer metric on a reader the benchmark
has are added the way a `model_config`, `perf_opt` or `tracing` PR must add
them — new files and appended manifest entries only, no edit to a file the
benchmark has. tests/benchmark/test_room_for_fields.py runs EVERY committed
cell's manifest function (tests/benchmark/manifest_rules.py) against the
copy, so a cell's test that pins a last place or a whole list fails in the
PR that writes it.

The deployment is `node_preset(3)` (one model a node, three metrics fused
into one 384-bit SDR, dense SP pool, u16 permanences, 4 segments a cell) at
6 groups x 1,024 nodes = 4.35 GiB of state, with `cluster-256`'s precision,
control and guarantees. The names are the rehearsal's own, so that it stays
true once a real cell of this family is committed: it then guards that the
harness takes a further one.

The live deployment is `cluster-32`'s model and layout under the rehearsal's
own name (128 groups x 1,024 streams, one row a stream a 1 s slot: the north
star's shape), on every list `cluster-256-live` is on; the further metric is
the one PERF.md s7 left for any PR to add: host ms a tick under
`rtap.state.relayout`, a data file on the `span_sum` reader."""

from __future__ import annotations

import json
import os
import shutil

from benchmark.registry import REPO

CELL, CONFIG = "rehearsal-fields-replay", "rehearsal-fields"
LIVE_CELL, LIVE_CONFIG = "rehearsal-served-live", "rehearsal-served"
LIVE_TRAFFIC = "rehearsal-live-1s"
LIVE_HEAD = "cluster-256-live"
#: the further per-layer metric, appended last
METRIC = "relayout_ms.rehearsal"
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


def live_lists(manifest: dict) -> list[dict]:
    """Every metric the accepted live cell reports by a list: the end-to-end
    one, the per-layer ones, the launcher's (`warm_compile_s` holds the
    replay cells too). A further live cell of scalar rows joins them all."""
    return [m for m in manifest["end_to_end"] + manifest["per_layer"]
            if LIVE_HEAD in m.get("workloads", ())]


def _load(*path: str) -> dict:
    with open(os.path.join(*path)) as f:
        return json.load(f)


def _dump(data: dict, *path: str) -> None:
    with open(os.path.join(*path), "w") as f:
        json.dump(data, f, indent=1)


def add_live(bdir: str, bm: dict, groups: int, group_size: int,
             mix: dict) -> None:
    """The live deployment and the further metric: three new files, and
    appended entries of `bm`."""
    cfg = _load(bdir, "configs", "cluster-32.json")
    cfg.update(name=LIVE_CONFIG, live_cadence_s=1.0, source=(
        "rtap_tpu docs/DEPLOYMENT.md s2 'The serve command at the 100k "
        "point'; BASELINE.json north_star (1 s cadence)"))
    cfg["layout"].update(groups=groups, group_size=group_size,
                         streams=groups * group_size)
    _dump(cfg, bdir, "configs", LIVE_CONFIG + ".json")
    live = _load(bdir, "traffic", "live-5s.json")
    live.update({"name": LIVE_TRAFFIC, "cadence_s": 1.0,
                 "phase_spread_s": 0.5, "guard_s": 0.25,
                 "trace_window_s": 2.0, **mix})
    _dump(live, bdir, "traffic", LIVE_TRAFFIC + ".json")
    definition = {"name": METRIC, "unit": "ms", "better": "lower",
                  "layer": "stream groups", "moves": "score_p50_ms",
                  "reader": "span_sum", "span": "rtap.state.relayout",
                  "per": "tick"}
    _dump(definition, bdir, "layer_metrics", METRIC + ".json")
    bm["configs"].append({
        "name": LIVE_CONFIG, "source": cfg["source"], "reduced": [],
        "file": f"benchmark/configs/{LIVE_CONFIG}.json",
        "why": f"the 100k-streams-per-chip model served live: {groups} "
               f"groups x {group_size}, one row a stream a second over TCP"})
    bm["workloads"].append({
        "name": LIVE_CELL, "config": LIVE_CONFIG, "traffic": LIVE_TRAFFIC,
        "chips": 1,
        "why": "one JSONL row a stream a 1 s slot over TCP, open loop, phase "
               "spread 0.5 s: ingest, loop, 128 one-tick programs a tick, "
               "emit; the host's cost a group has its largest share"})
    for m in live_lists(bm):
        m["workloads"].append(LIVE_CELL)
    bm["per_layer"].append(
        {"name": METRIC, "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "stream groups",
         "moves": "score_p50_ms",
         "workloads": [LIVE_HEAD, "node-3-live", LIVE_CELL]})


def make_root(tmp_path, groups: int = 6, group_size: int = 1024,
              live_groups: int = 128, live_group_size: int = 1024,
              live_mix: dict | None = None, **keys) -> str:
    """-> the root of the copy. `keys` are further keys of the multi-field
    configuration's file (`correct_ticks`, `correct_sample_streams`);
    `live_mix` overrides keys of the live traffic file (a tiny rig's wide
    margins)."""
    from rtap_tpu.config import node_preset

    root = os.path.join(str(tmp_path), "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmark")
    accepted = _load(bdir, "configs", "cluster-256.json")
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
    _dump(cfg, bdir, "configs", CONFIG + ".json")
    for name, (what, scope) in ROOFLINES.items():
        definition = {"name": name, "unit": "%", "better": "higher",
                      "layer": "kernels", "moves": "metrics_per_s",
                      "reader": "dense_roofline", "what": what,
                      "module": "jit_chunk_step"}
        if scope:
            definition["scope"] = scope
        _dump(definition, bdir, "layer_metrics", name + ".json")

    bm = _load(REPO, "BENCHMARK.json")
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
    add_live(bdir, bm, live_groups, live_group_size, live_mix or {})
    _dump(bm, root, "BENCHMARK.json")
    return root



C32_CELL, C32_TRAFFIC = "cluster-32-live-tiny", "live-tiny-1s"


def make_cluster_32_live_root(tmp_path, groups: int = 2,
                              group_size: int = 8) -> str:
    """`cluster-32` x a live mix, as data files: the committed benchmark
    under a temp root, a traffic file of kind `live` at the configuration's
    stated cadence and the cell appended to the manifest — what PERF.md s7's
    first cell needs from a later PR. For the CPU the configuration's layout
    is cut to a stream count it holds and the mix has the tiny rig's wide
    margins; every width and the stated `live_cadence_s` stay."""
    from tests.benchmark.tiny import TINY_LIVE

    root = os.path.join(str(tmp_path), "checkout")
    bdir = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), bdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = os.path.join(bdir, "configs", "cluster-32.json")
    cfg = _load(path)
    cfg["layout"].update(groups=groups, group_size=group_size,
                         streams=groups * group_size)
    cfg["correct_sample_streams"] = 6
    _dump(cfg, path)
    live = _load(bdir, "traffic", "live-5s.json")
    live.update(name=C32_TRAFFIC, **TINY_LIVE)
    _dump(live, bdir, "traffic", C32_TRAFFIC + ".json")
    bm = _load(REPO, "BENCHMARK.json")
    bm["workloads"].append({"name": C32_CELL, "config": "cluster-32",
                            "traffic": C32_TRAFFIC, "chips": 1, "why": "t"})
    for m in live_lists(bm):
        m["workloads"].append(C32_CELL)
    _dump(bm, root, "BENCHMARK.json")
    return root
