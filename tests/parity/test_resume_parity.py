"""A group saved and loaded back continues bit-identically (ISSUE 46): replay W
ticks in chunks, `save_group`, drop the group, `load_group`, then serve T
one-tick steps — raw scores and every permanence row equal a `StreamGroup`
that never stopped and the numpy oracle (a cpu-backend group), bit for bit,
in each of the three forms the device holds a model's pools in: narrow rows
of 192 lanes (a cluster preset), of 384 (the node model, gathered by
compare-select), and the NAB family's wide rows. A load re-lays the pools
once and holds no second state; a checkpoint with one permanence quantum
flipped does not pass."""

import copy
import json
import os

import numpy as np
import pytest

from benchmark.check import PERM_LEAVES
from benchmark.registry import REPO
from rtap_tpu.config import ModelConfig, node_preset, scaled_cluster_preset
from rtap_tpu.obs import TraceRecorder
from rtap_tpu.ops import tm_tpu
from rtap_tpu.service.checkpoint import load_group, save_group
from rtap_tpu.service.registry import StreamGroup
from tests.benchmark.tiny_nab import TINY_MODEL

G, CHUNK, W, T, SEED = 2, 8, 24, 6, 46


def tiny_nab() -> ModelConfig:
    with open(os.path.join(REPO, "benchmark", "configs", "nab-2048.json")) as f:
        model = copy.deepcopy(json.load(f)["model"])
    for section, sizes in TINY_MODEL.items():
        model[section].update(sizes)
    return ModelConfig.from_dict(model)


#: name -> (config, lanes of a pool row, wide rows?)
FORMS = {
    "cluster_192": (lambda: scaled_cluster_preset(32), 192, False),
    "node_384": (lambda: node_preset(3), 384, False),
    "nab_wide": (tiny_nab, 2048, True),
}


def feed(cfg: ModelConfig):
    rng = np.random.default_rng(SEED)
    n = W + T
    values = (50 + 30 * np.sin(np.arange(n)[:, None, None] / 3.0)
              + rng.normal(0, 2, (n, G, cfg.n_fields))).astype(np.float32)
    ts = 1_700_000_000 + np.arange(n)[:, None] + np.zeros((1, G), np.int64)
    return values, ts


def group(cfg, backend="tpu") -> StreamGroup:
    return StreamGroup(cfg, [f"s{i}" for i in range(G)], seed=SEED,
                       backend=backend, threshold=0.5, debounce=2)


def replay(grp, values, ts, t0, t1, chunk) -> np.ndarray:
    """Ticks t0..t1 in chunks of `chunk` -> raw [t1 - t0, G]."""
    return np.concatenate([
        grp.run_chunk(values[i:i + chunk], ts[i:i + chunk])[0]
        for i in range(t0, t1, chunk)])


def perm_rows(grp) -> dict:
    if grp.backend == "cpu":
        return {k: np.stack([s[k] for s in grp._states]) for k in PERM_LEAVES}
    return {k: np.asarray(grp.state[k]) for k in PERM_LEAVES}


@pytest.fixture(scope="module", params=list(FORMS))
def case(request, tmp_path_factory):
    """One form: the uninterrupted group's and the oracle's scores and
    permanences over W + T ticks, and a checkpoint written after W."""
    make, lanes, wide = FORMS[request.param]
    cfg = make()
    tm = cfg.tm
    assert tm.cells_per_column * tm.max_segments_per_cell \
        * tm.max_synapses_per_segment == lanes
    assert tm_tpu.wide_rows(tm) == wide
    values, ts = feed(cfg)
    whole = group(cfg)
    raw_whole = np.concatenate([replay(whole, values, ts, 0, W, CHUNK),
                                replay(whole, values, ts, W, W + T, 1)])
    oracle = group(cfg, backend="cpu")
    raw_oracle = replay(oracle, values, ts, 0, W + T, 1)
    path = str(tmp_path_factory.mktemp("resume_" + request.param) / "group0000")
    warmed = group(cfg)
    raw_warm = replay(warmed, values, ts, 0, W, CHUNK)
    trace = TraceRecorder(capacity=64)
    save_group(warmed, path, alerts_offset=123, trace=trace)
    del warmed
    return {"cfg": cfg, "values": values, "ts": ts, "path": path,
            "trace": trace, "raw_warm": raw_warm, "raw_whole": raw_whole,
            "raw_oracle": raw_oracle, "perm_whole": perm_rows(whole),
            "perm_oracle": perm_rows(oracle)}


def test_a_loaded_group_continues_bit_identically(case):
    resumed = load_group(case["path"], trace=case["trace"])
    # the load re-laid the pools once, and made no state of its own first
    assert resumed.relayouts == 1
    assert resumed.ticks == W and resumed.resume_alerts_offset == 123
    raw = replay(resumed, case["values"], case["ts"], W, W + T, 1)
    served = np.concatenate([case["raw_warm"], raw])
    np.testing.assert_array_equal(served, case["raw_whole"])
    np.testing.assert_array_equal(served, case["raw_oracle"])
    got = perm_rows(resumed)
    for leaf in PERM_LEAVES:
        np.testing.assert_array_equal(got[leaf], case["perm_whole"][leaf], leaf)
        np.testing.assert_array_equal(got[leaf], case["perm_oracle"][leaf], leaf)
    # the ticks really learned after the load: moved state is compared
    assert not np.array_equal(
        got["syn_perm"], np.asarray(load_group(case["path"]).state["syn_perm"]))
    # one span a save and a load, in the recorder the caller handed over
    names = [r["name"] for r in case["trace"].records() if r["kind"] == "span"]
    assert names.count("checkpoint_save") == 1
    assert names.count("checkpoint_load") == 1


def test_one_flipped_permanence_quantum_does_not_pass(case, tmp_path):
    import shutil

    import orbax.checkpoint as ocp

    path = tmp_path / "group0000"
    shutil.copytree(case["path"], path)
    with ocp.PyTreeCheckpointer() as ckptr:
        tree = ckptr.restore(path / "state")
    perm = np.array(tree["model"]["syn_perm"])
    quantum = 1 if perm.dtype.kind == "u" else np.float32(1 / 65535)
    flat = perm.reshape(-1)
    at = int(np.argmax(flat > 0))  # a synapse that exists
    flat[at] = flat[at] - quantum
    tree["model"]["syn_perm"] = perm
    shutil.rmtree(path / "state")
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(path / "state", tree, force=True)
    resumed = load_group(path)
    replay(resumed, case["values"], case["ts"], W, W + T, 1)
    assert not np.array_equal(np.asarray(resumed.state["syn_perm"]),
                              case["perm_whole"]["syn_perm"])
