"""The tiny twin of `nab-2048` for the CPU: the real harness, the real
`replay` kind and the real manifest under a temp root, with the one
configuration cut to a size the CPU holds and everything that makes the
family kept — dense SP pool, f32 permanences, time-of-day field, 32 cells a
column, pool rows on the wide side of ops/tm_tpu.py's shape line."""

from __future__ import annotations

import json
import os
import shutil

from benchmark.registry import REPO

CELL, CONFIG = "nab-2048-replay", "nab-2048"

#: 96 columns x 32 cells x 4 segments x 16 synapses (2,048 lanes a row: wide);
#: thresholds at scaled_nab_preset(96)'s ratios
TINY_MODEL = {
    "sp": {"columns": 96, "num_active_columns": 4},
    "tm": {"max_segments_per_cell": 4, "max_synapses_per_segment": 16,
           "activation_threshold": 2, "min_threshold": 1,
           "new_synapse_count": 3, "col_cap": 4},
}


def make_root(tmp_path, streams: int = 3, **keys) -> str:
    """`keys` are further keys of the configuration file (`correct_ticks`)."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    path = os.path.join(root, "benchmark", "configs", CONFIG + ".json")
    with open(path) as f:
        cfg = json.load(f)
    for section, sizes in TINY_MODEL.items():
        cfg["model"][section].update(sizes)
    cfg["layout"].update(groups=1, group_size=streams, streams=streams)
    cfg.update(keys)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


def run(root: str, seed: int, seconds: float, **kw):
    """run_cell without the look for a chip -> (result, record)."""
    from benchmark.run import run_cell

    rc, result, record = run_cell(CELL, seed, seconds, False, root=root,
                                  allow_cpu=True, **kw)
    assert rc == 0
    return result, record
