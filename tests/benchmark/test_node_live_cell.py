"""The cell `node-3-live` (ISSUE 43), held on the CPU: the committed
configuration `node-3-served` is `node_preset(3)` with nothing overridden,
states its record, cadence and guarantees and fills over a quarter of the
chip; the manifest lists the cell on all 25 live lists ISSUE 43 named (13
of them since ISSUE 45), with its three per-layer metrics read through their
readers; the offered records are a pure function of the seed with `null` in
exactly the stated share; and the cell cut to a tiny node count runs through
the unedited harness over a real socket — correct, not correct under its u8
control, and with two fields swapped on the way to the loop every row is
misrouted."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import kernel_bytes_dense as kbd
from benchmark.feed import stream_ids
from benchmark.generator_fields import build_payloads, offered_records
from benchmark.registry import REPO, Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark.test_nab_cell import hand_made_record
from tests.benchmark.test_room_for_fields import OPS
from tests.benchmark.tiny import TINY_LIVE, failed_numbers, run

CELL, CONFIG, TRAFFIC = "node-3-live", "node-3-served", "live-fields-1s"
SEED = 4_430_000_001  # beyond 2**31, like the driver's
SECONDS, N, S, F = 4.6, 4, 8, 3  # 4 slots of 1.0 s; 2 groups x 4 nodes
#: the live lists ISSUE 43 named. Twelve took the cell at PR 43; the other
#: thirteen (`warm_compile_s` among them, whose list holds the replay cells
#: too) were held shut by the accepted tests' last places and whole lists
#: until ISSUE 45 wrote the rule (tests/benchmark/manifest_rules.py)
TAKEN = {"group_fetch_ms.live", "group_likelihood_ms.live", "ingest_lag_ms",
         "gen_late_ms", "missed_tick_share", "collect_wait_ms",
         "loop_host_ms", "score_p95_ms.live", "detect_p50_ms.live",
         "detect_p95_ms.live", "step_device_ms.live",
         "device_idle_share.live"}
THIRTEEN = {"loop_dispatch_ms", "loop_emit_ms", "tick_exposed_host_ms.live",
            "group_queue_ms.live", "group_fetch_tail_ms.live",
            "ingest_feed_ms", "ingest_snapshot_ms", "host_gc_ms.live",
            "aot_warm_s", "tm_learn_ms.live", "tm_dendrite_ms.live",
            "unscoped_ms.live", "warm_compile_s"}
#: the cells a list held when this one joined it
HEADS = {"warm_compile_s": ["cluster-256-replay", "cluster-32-replay",
                            "cluster-256-live", "nab-2048-replay",
                            "node-3-replay"]}
NEW = {"ingest_feed_us_per_value.live": ("span_per_count", "ingest"),
       "group_stage_ms.live": ("group_phase", "stream groups"),
       "tm_roofline.node.live": ("dense_roofline", "kernels")}


def committed(sub: str, name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def make_root(tmp_path, groups: int = 2, group_size: int = 4) -> str:
    """The committed benchmark under a temp root, the configuration cut to a
    node count the CPU holds and the mix to the tiny rig's wide margins;
    every width, the record and the null share stay."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    cfg = committed("configs", CONFIG)
    cfg["layout"].update(groups=groups, group_size=group_size,
                         streams=groups * group_size)
    cfg["correct_sample_streams"] = 4
    mix = committed("traffic", TRAFFIC)
    mix.update(TINY_LIVE, null_share=0.25)
    for sub, name, data in (("configs", CONFIG, cfg), ("traffic", TRAFFIC, mix)):
        with open(os.path.join(root, "benchmark", sub, name + ".json"), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_node_live"))


# ---- the committed files ----

def test_config_file_is_the_preset_and_states_the_deployment():
    from rtap_tpu.config import node_preset

    cfg, replay = committed("configs", CONFIG), committed("configs", "node-3")
    assert cfg["model"] == node_preset(3).to_dict() == replay["model"]
    assert cfg["reduced"] == [] and cfg["live_cadence_s"] == 1.0
    assert cfg["record"]["fields"] == ["cpu", "mem", "net"]
    assert '"values": [cpu, mem, net]' in cfg["record"]["format"]
    assert {"learn_cap", "other_workspace_bounds", "rdse", "fields",
            "null_share", "streams_resident", "seed"} <= set(cfg["assumed"])
    assert cfg["layout"] == {**replay["layout"], "note": cfg["layout"]["note"]}
    # precision, limits, control and sample as node-3.json states them; the
    # guarantees say more (the record whole, null as missing), none less
    for key in ("precision", "control", "correct_sample_streams"):
        assert cfg[key] == replay[key], key
    assert set(cfg["guarantees"]) == set(replay["guarantees"]) | \
        {"record", "missing"}
    for key in ("learning", "capacity"):
        assert cfg["guarantees"][key] == replay["guarantees"][key]
    assert "never as 0" in cfg["guarantees"]["missing"]
    assert cfg["source"] != replay["source"] and len(cfg["source"]) <= 200


def test_state_on_the_device_is_over_a_quarter_of_the_chip():
    cfg = committed("configs", CONFIG)
    per_node = kbd.state_bytes_per_stream(cfg["model"])
    share = cfg["layout"]["streams"] * per_node / (16 * 2 ** 30)
    assert per_node == 760_871 and cfg["layout"]["streams"] == 8192
    assert share >= 0.25 and share == pytest.approx(0.3628, abs=1e-4)


def manifest_holds(reg: Registry) -> None:
    """What this cell's test holds of a manifest (tests/benchmark/
    manifest_rules.py): the committed one, and the rehearsal's copy."""
    entry = rules.entry(reg.manifest["configs"], CONFIG)
    cfg = committed("configs", CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    rules.cell_entry(reg, CELL, CONFIG, TRAFFIC)
    assert len(entry["why"]) <= 200
    mix = reg.cell(CELL)["traffic"]
    assert (mix["kind"], mix["cadence_s"], mix["phase_spread_s"],
            mix["guard_s"], mix["null_share"]) == \
        ("live_fields", 1.0, 0.5, 0.25, 0.01)
    live = committed("traffic", "live-5s")
    for key in ("hold_until_snapshot", "send_quantum_s", "pipeline_depth",
                "micro_chunk", "learn", "drain_cadences", "row_ts_base"):
        assert mix[key] == live[key], key
    rules.reports_at_least(reg, CELL, "end_to_end",
                           {"score_p50_ms", "setup_s", "peak_bytes_per_stream"})
    layer = rules.reports_at_least(reg, CELL, "per_layer",
                                   TAKEN | THIRTEEN | set(NEW))
    for name in TAKEN | THIRTEEN:  # right after the cells accepted before it
        rules.listed_after(layer[name]["workloads"],
                           HEADS.get(name, ["cluster-256-live"]), CELL)
        rules.agrees_with_definition(reg, layer[name])
    rules.added_in_order(reg.manifest["per_layer"], NEW,
                         after=TAKEN | THIRTEEN)
    for name, (reader, where) in NEW.items():
        definition = rules.agrees_with_definition(reg, layer[name])
        assert definition["reader"] == reader
        rules.listed_after(layer[name]["workloads"], [], CELL)
        assert (layer[name]["layer"], layer[name]["moves"]) == \
            (where, "score_p50_ms")


def test_manifest_lists_the_cell_on_every_live_list():
    manifest_holds(Registry())


# ---- the three new metrics through their readers ----

def note(name, start, dur, **args):
    return [name, start, dur, args]


def test_the_parsers_cost_a_value_is_span_time_over_the_values_it_wrote():
    definition, module = Registry().layer_metric("ingest_feed_us_per_value.live")
    feeds = [note("rtap.ingest.feed", 2_000, 30_000, bytes=900, values=24,
                  nulls=0),
             note("rtap.ingest.feed", 40_000, 18_000, bytes=500, values=11,
                  nulls=1),
             # outside the window on either side: not counted
             note("rtap.ingest.feed", 500, 9_000, values=3, nulls=0),
             note("rtap.ingest.feed", 9_990_000, 20_000, values=3, nulls=0)]
    planes = {"/host:CPU": {"annotations": feeds + [
        note("bench_sync", 1_000, 1_000)]}}
    record = {"trace": {"window_s": 0.01}, "scoped_planes": planes}
    assert module.read(record, definition) == pytest.approx(48.0 / 35)
    assert module.read({"trace": None}, definition) is None
    # a program whose span says nothing of values (the parent), a window the
    # span did no work in, a trace without the span: nothing, never 0
    del feeds[1][3]["values"]
    assert module.read(record, definition) is None
    feeds[0][3]["values"], feeds[1][3]["values"] = 0, 0
    assert module.read(record, definition) is None
    planes["/host:CPU"]["annotations"] = [note("bench_sync", 1_000, 1_000)]
    assert module.read(record, definition) is None


def test_stage_and_tm_share_read_through_the_readers_the_benchmark_has():
    reg = Registry()
    record = hand_made_record(committed("configs", CONFIG), OPS)
    record["scoped_planes"]["/host:CPU"]["annotations"] += [
        note("rtap.group.stage", 1_000 + 500 * i, 200 + 100 * (i % 2),
             group=f"g{i % 2}", seq=i // 2) for i in range(4)]

    def read(name, rec=record):
        definition, module = reg.layer_metric(name)
        return module.read(rec, definition)

    # two groups, two ticks: (200 + 300) ns a tick
    assert read("group_stage_ms.live") == pytest.approx(500 / 1e6)
    # the one-tick program's TM against the floor `tm_roofline.node` reads
    # the chunk's against (OPS: ns per 2-tick program -> ms per tick)
    floor_ms = kbd.kernel_floor_seconds(
        "rtap.tm", record["config"]["model"], 1024, "TPU v5 lite") * 1e3
    assert floor_ms == pytest.approx(0.6686, abs=5e-5)
    assert read("tm_roofline.node.live") == pytest.approx(
        100 * floor_ms / ((1400 + 200 + 1200) / 2 / 1e6))
    assert read("tm_roofline.node.live") == read("tm_roofline.node")
    for name in NEW:
        assert read(name, {"trace": None}) is None, name


# ---- the offered set ----

def test_offered_records_are_a_pure_function_of_the_seed():
    spread, quantum = 0.5, 0.005
    a, phi, send = offered_records(SEED, 64, 50, F, 0.01, spread, quantum)
    b, _, _ = offered_records(SEED, 64, 50, F, 0.01, spread, quantum)
    c, phi_c, _ = offered_records(SEED + 1, 64, 50, F, 0.01, spread, quantum)
    assert a.shape == (50, 64, F) and a.dtype == np.float32
    assert np.array_equal(a, b, equal_nan=True)
    assert not np.array_equal(a, c, equal_nan=True)
    # the same even grid of due offsets, dealt in another order
    assert sorted(phi) == sorted(phi_c) and not np.array_equal(phi, phi_c)
    assert 0 < phi.min() and phi.max() < spread and (send >= phi).all()
    # exactly the stated share of the records carry exactly one null
    nulls = np.isnan(a).sum(axis=2)
    assert set(np.unique(nulls)) == {0, 1} and nulls.sum() == 32
    assert len({int(f) for f in np.nonzero(np.isnan(a))[2]}) == F
    # every field a signal of its own
    filled = np.where(np.isnan(a), 0, a)
    assert not np.allclose(filled[..., 0], filled[..., 1])


def test_payloads_are_the_offered_records_on_the_wire():
    values, _phi, _send = offered_records(SEED, 6, 3, F, 0.25, 0.3, 0.15)
    offsets, payloads, rows, _phi, batch_of = build_payloads(
        SEED, 6, 3, 0.3, 0.15, 2_000_000_000, n_fields=F, null_share=0.25)
    assert rows.sum() == 6 and len(offsets) == len(rows) == len(payloads[0])
    ids = stream_ids(6)
    for k in range(3):
        recs = [json.loads(line) for batch in payloads[k]
                for line in batch.decode().splitlines()]
        assert sorted(r["id"] for r in recs) == ids
        assert all(r["ts"] == 2_000_000_000 + k for r in recs)
        for r in recs:
            want = values[k, ids.index(r["id"])]
            got = np.array([np.nan if v is None else v for v in r["values"]],
                           np.float32)
            assert np.array_equal(got, want, equal_nan=True)
    assert b"null" in b"".join(b for slot in payloads for b in slot)
    assert b"NaN" not in b"".join(b for slot in payloads for b in slot)


# ---- the cell through benchmark.run, at a tiny node count ----

def test_tiny_cell_serves_every_record_and_is_correct(root):
    result, record = run(root, CELL, SEED, SECONDS)
    assert result["correct"], result["compared"]
    assert result["attempted"] == N * S and result["failed"] == 0
    assert set(result["metrics"]) == {"score_p50_ms", "peak_bytes_per_stream",
                                      "setup_s"}
    # every record scored by the tick after its slot, whole: the sampled
    # nodes' fed rows are the offered ones, NaN where null was offered
    assert (record["scored_tick"] == np.arange(1, N + 1)[:, None]).all()
    sent, _phi, _send = offered_records(
        SEED, S, N, F, 0.25, TINY_LIVE["phase_spread_s"],
        TINY_LIVE["send_quantum_s"])
    assert np.isnan(sent).sum() == N * S // 4
    for s in record["sample"]:
        assert s["values"].shape == (N + 1, F)
        assert np.isnan(s["values"][0]).all()  # the priming tick: nothing yet
        assert np.array_equal(s["values"][1:], sent[:, s["stream"]],
                              equal_nan=True)
    assert record["generator"]["rows_sent"] == N * S
    assert record["loop_stats"]["missed_deadlines"] == 0


def test_tiny_cell_under_its_u8_control_is_not_correct(root):
    control, _ = run(root, CELL, SEED, SECONDS, control=True)
    assert not control["correct"] and control["failed"] == 0
    assert "perm_max_frac_diff" in failed_numbers(control)


def test_two_fields_swapped_on_the_way_to_the_loop_are_misrouted(root,
                                                                 monkeypatch):
    from rtap_tpu.service.sources import TcpJsonlSource

    inner = TcpJsonlSource.__call__

    def swapped(self, tick):
        values, ts = inner(self, tick)
        return values[:, [1, 0, 2]], ts

    monkeypatch.setattr(TcpJsonlSource, "__call__", swapped)
    result, record = run(root, CELL, SEED, SECONDS)
    assert not result["correct"]
    assert failed_numbers(result) == {"rows_misrouted"}
    assert record["rows_misrouted"] >= N * S and result["failed"] == N * S
