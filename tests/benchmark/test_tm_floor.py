"""The temporal memory's one memory floor (`KERNELS["rtap.tm"]` in both byte
tables) and the three shares that read it: a floor asks for no leaf twice in
one direction and for no more than one pass over the state, its value in the
four committed configurations is the one PERF.md quotes, and both roofline
readers divide it by the time of every `rtap.tm.*` scope together — so the
share is the same whichever scope a fusion's root files the pool sweep
under (ledger, PR 40: `tm_learn_roofline.nab` read 109.06 % for a sound
change)."""

import json
import os

import pytest

from benchmark import kernel_bytes as kbs
from benchmark import kernel_bytes_dense as kbd
from benchmark.registry import REPO, Registry
from benchmark.scoped_trace import scope_with_subscopes_ms
from tests.benchmark.test_nab_cell import hand_made_record

#: configuration -> (its family's table, bytes a stream-tick, ms a group-tick)
FLOORS = {"cluster-256": (kbs, 272_640, 0.3409),
          "cluster-32": (kbs, 34_080, 0.0426),
          "nab-2048": (kbd, 285_542_400, 5.927),
          "node-3": (kbd, 534_784, 0.6686)}
HANDED_OVER = ("sdr", "overlap", "active_cols", "active_cells")

#: device ms a group-tick of PR 40's refused change in `nab-2048-replay`
#: (ledger, PR 40: `tm_activate_ms.replay`, `tm_learn_ms.replay`,
#: `tm_learn_rows_ms.nab`, `tm_dendrite_ms.replay`), beside two scopes that
#: are not the TM's
PR40_TABLE = {"rtap.tm.activate": 0.863, "rtap.tm.learn": 8.4187,
              "rtap.tm.learn.rows": 2.0217, "rtap.tm.dendrite": 30.696,
              "rtap.sp.learn": 0.196, "unscoped": 6.346}


def config(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def record_with_table(name: str, table: dict) -> dict:
    """A traced run's record whose scope table is `table` (the readers take
    it from the record's cache: benchmark/scoped_trace.py:scope_table)."""
    record = hand_made_record(config(name))
    record["scope_tables"] = {"jit_chunk_step": dict(table)}
    return record


def read(record: dict, name: str):
    definition, reader = Registry().layer_metric(name)
    return reader.read(record, definition)


@pytest.mark.parametrize("name", list(FLOORS))
def test_the_floor_asks_for_one_pass_at_the_most(name):
    table, _, _ = FLOORS[name]
    assert "rtap.tm" in table.KERNELS
    assert not {"rtap.tm.learn", "rtap.tm.dendrite"} & set(table.KERNELS)
    assert kbd.KERNELS["rtap.tm"] is kbs.KERNELS["rtap.tm"]  # leaf for leaf
    model = config(name)["model"]
    leaves = table.leaf_bytes(model)
    read_, written = table.KERNELS["rtap.tm"]
    for direction in (read_, written):
        assert len(set(direction)) == len(direction), direction
        assert set(direction) <= set(leaves)
    # every synapse is tested, so both pools are read; neither is written
    # whole: learning moves learn_cap segments' rows at the most
    assert {"presyn", "syn_perm"} <= set(read_)
    assert not {"presyn", "syn_perm"} & set(written)
    # one pass at the most in each direction, and under the whole step's
    # floor (the state read once and written once)
    state = sum(leaves[k] for k in table.STATE_LEAVES)
    one_pass = state + sum(leaves[k] for k in HANDED_OVER)
    for direction in (read_, written):
        assert sum(leaves[k] for k in direction) <= one_pass
    assert table.kernel_bytes_per_stream("rtap.tm", model) < 2 * state


@pytest.mark.parametrize("name", list(FLOORS))
def test_the_floor_of_each_committed_configuration(name):
    table, nbytes, floor_ms = FLOORS[name]
    cfg = config(name)
    assert table.kernel_bytes_per_stream("rtap.tm", cfg["model"]) == nbytes
    assert 1e3 * table.kernel_floor_seconds(
        "rtap.tm", cfg["model"], cfg["layout"]["group_size"], "TPU v5 lite") \
        == pytest.approx(floor_ms, abs=5e-5 if floor_ms < 1 else 5e-4)


def test_pr40s_times_read_a_seventh_of_the_floor_not_over_it():
    record = record_with_table("nab-2048", PR40_TABLE)
    assert record["config"]["layout"]["group_size"] == 17
    assert scope_with_subscopes_ms(PR40_TABLE, "rtap.tm") == \
        pytest.approx(0.863 + 8.4187 + 2.0217 + 30.696)
    assert read(record, "tm_roofline.nab") == pytest.approx(14.1, abs=0.1)
    # the scope the refused reading was about: its own time, with its rows
    assert scope_with_subscopes_ms(PR40_TABLE, "rtap.tm.learn") == \
        pytest.approx(8.4187 + 2.0217)
    # where the sweep's time is filed does not move the share
    moved = dict(PR40_TABLE)
    moved["rtap.tm.learn"] += moved.pop("rtap.tm.dendrite")
    assert read(record_with_table("nab-2048", moved), "tm_roofline.nab") == \
        pytest.approx(read(record, "tm_roofline.nab"))


def test_both_readers_sum_a_scope_with_its_sub_scopes():
    # a name that only starts like the scope is another scope
    table = {**PR40_TABLE, "rtap.tmx": 99.0, "rtap.sp.learn.rows": 0.004}
    tm_ms = 0.863 + 8.4187 + 2.0217 + 30.696
    for cell, name, module, group in (
            ("cluster-256", "tm_roofline.replay", kbs, 1024),
            ("node-3", "tm_roofline.node", kbd, 1024),
            ("nab-2048", "tm_roofline.nab", kbd, 17)):
        record = record_with_table(cell, table)
        floor = module.kernel_floor_seconds(
            "rtap.tm", record["config"]["model"], group, "TPU v5 lite")
        assert read(record, name) == pytest.approx(
            100 * floor / (tm_ms / 1e3)), name
    for cell, name, module in (
            ("cluster-256", "sp_learn_roofline.replay", kbs),
            ("node-3", "sp_learn_roofline.node", kbd)):
        record = record_with_table(cell, table)
        floor = module.kernel_floor_seconds(
            "rtap.sp.learn", record["config"]["model"], 1024, "TPU v5 lite")
        assert read(record, name) == pytest.approx(
            100 * floor / ((0.196 + 0.004) / 1e3)), name
    # no TM scope in the table: nothing to read, never 0 and never an error
    bare = {"rtap.sp.learn": 0.196, "unscoped": 6.346}
    for cell, name in (("cluster-256", "tm_roofline.replay"),
                       ("nab-2048", "tm_roofline.nab")):
        assert read(record_with_table(cell, bare), name) is None
