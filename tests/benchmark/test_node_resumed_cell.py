"""The cell `node-3-resumed-live` (ISSUE 46), held on the CPU: the committed
configuration `node-3-resumed` is `node-3-served`'s model key for key, states
its warm-up, its alerting and its guarantees and fills over a quarter of the
chip; the manifest lists the cell on every list `node-3-live` is on, with its
seven per-layer metrics read through readers the benchmark has; the offered
fleet is a pure function of the seed, its faults in the stated shares and a
killed node's records never offered; the reference's likelihood is the
program's, and its alert rule holds on a hand-made series; and the cell cut to
a tiny fleet runs through the unedited harness — warm, save, release, load,
serve over a real socket — correct, not correct under its u8 control, and
with an alert line dropped and another doubled each counted in `failed`."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import kernel_bytes_dense as kbd
from benchmark.feed import stream_ids
from benchmark.generator_faults import build_payloads, draw_faults, offered_fleet
from benchmark.reference import likelihood as ref_likelihood
from benchmark.registry import REPO, Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark.test_node_live_cell import NEW as LIVE_NEW
from tests.benchmark.test_node_live_cell import TAKEN, THIRTEEN
from tests.benchmark.tiny import TINY_LIVE, failed_numbers, run

CELL, CONFIG = "node-3-resumed-live", "node-3-resumed"
TRAFFIC, KIND = "live-fields-faults-1s", "live_resumed"
SEED = 4_460_000_001  # beyond 2**31, like the driver's
SECONDS, N, S, F = 6.6, 6, 8, 3  # 6 slots of 1.0 s; 2 groups x 4 nodes
#: the tiny fleet's likelihood: a probation the CPU reaches, then whole chunks
TINY_LIKELIHOOD = {"learning_period": 24, "estimation_samples": 8}
TINY_RESUME = {"history_margin_ticks": 8, "history_ticks": 40}
TINY_FAULTS = {"fault_node_share": 0.5, "fault_onset_slots": "0-2",
               "fault_duration_slots": 2}
#: the 28 per-layer lists `node-3-live` was on when this cell joined them
JOINED = TAKEN | THIRTEEN | set(LIVE_NEW)
#: per-layer metrics the cell brought -> (reader, layer, moves)
NEW = {"warm_replay_s": ("bench_span", "launcher", "setup_s"),
       "checkpoint_save_s": ("bench_span", "launcher", "setup_s"),
       "checkpoint_load_s": ("host_span_sum", "launcher", "setup_s"),
       "resume_first_tick_s": ("bench_span", "launcher", "setup_s"),
       "loop_alert_ms": ("span_sum", "serving loop", "score_p50_ms"),
       "alert_line_p50_ms.live": ("row_latency", "serving loop", "score_p50_ms"),
       "alert_line_p95_ms.live": ("row_latency", "serving loop", "score_p50_ms")}


def committed(sub: str, name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def make_root(tmp_path, groups: int = 2, group_size: int = 4) -> str:
    """The committed benchmark under a temp root: the fleet cut to a node
    count the CPU holds, the likelihood's probation (and so the history) to
    a length it reaches, the threshold to one a tiny fleet crosses, and the
    mix to the tiny rig's wide margins; every width and the record stay."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    cfg = committed("configs", CONFIG)
    cfg["layout"].update(groups=groups, group_size=group_size,
                         streams=groups * group_size)
    cfg["correct_sample_streams"] = groups * group_size  # nearly every node
    cfg["model"]["likelihood"].update(TINY_LIKELIHOOD)
    cfg["resume"].update(TINY_RESUME)
    # under the probation's 0.0301: every tick of the probation alerts, and
    # after it a tick whose likelihood passes 0.37 — lines on both sides of
    # the restart, and ticks on both sides of the rule
    cfg["alerting"]["threshold"] = 0.02
    mix = committed("traffic", TRAFFIC)
    mix.update(TINY_LIVE, null_share=0.25)
    mix["faults"].update(TINY_FAULTS)
    for sub, name, data in (("configs", CONFIG, cfg), ("traffic", TRAFFIC, mix)):
        with open(os.path.join(root, "benchmark", sub, name + ".json"), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_node_resumed"))


@pytest.fixture(scope="module")
def served(root):
    """One run of the tiny cell -> (result, record)."""
    return run(root, CELL, SEED, SECONDS)


# ---- the committed files ----

def test_config_file_is_the_served_model_and_states_the_deployment():
    from rtap_tpu.config import LikelihoodConfig, node_preset

    cfg, live = committed("configs", CONFIG), committed("configs", "node-3-served")
    assert cfg["model"] == live["model"] == node_preset(3).to_dict()
    assert cfg["reduced"] == [] and cfg["live_cadence_s"] == 1.0
    for key in ("record", "control", "layout"):
        want = {**live[key], "note": cfg[key]["note"]} if key == "layout" \
            else live[key]
        assert cfg[key] == want, key
    assert cfg["source"] != live["source"] and len(cfg["source"]) <= 200
    # the history: the likelihood's own probation + the repo's settling
    # margin, in whole chunks — computed, and equal to the number stated
    kind = Registry().cell(CELL)["kind"]
    lik = LikelihoodConfig(**cfg["model"]["likelihood"])
    resume = cfg["resume"]
    assert ref_likelihood.probation(cfg["model"]["likelihood"]) \
        == lik.probationary_period == 400
    assert resume["history_margin_ticks"] == 100  # safe_inject_frac's margin
    assert kind.history_ticks(cfg) == resume["history_ticks"] == 504
    assert resume["history_ticks"] % resume["chunk_ticks"] == 0
    with pytest.raises(ValueError, match="history_ticks"):
        kind.history_ticks({**cfg, "resume": {**resume, "history_ticks": 496}})
    # serve's own alerting (rtap_tpu/__main__.py --threshold, --debounce)
    alerting = cfg["alerting"]
    assert (alerting["threshold"], alerting["debounce"],
            alerting["alert_flush_every"], alerting["latency"]) == (0.5, 2, 1, True)
    # node-3-served's guarantees and precision, none less; resume and alerts
    assert set(cfg["guarantees"]) == set(live["guarantees"]) | {"resume", "alerts"}
    for key in ("delivery", "record", "missing", "learning", "capacity", "state"):
        assert cfg["guarantees"][key] == live["guarantees"][key]
    assert {k: v for k, v in cfg["precision"].items()
            if not k.startswith("alert_")} == live["precision"]
    assert 0 < cfg["precision"]["alert_epsilon"] <= 1e-4
    assert set(live["assumed"]) | {"faults", "history", "sample"} \
        == set(cfg["assumed"])
    assert cfg["correct_sample_streams"] == 32 and "correct_ticks" not in cfg


def test_serves_defaults_are_the_files_alerting():
    import argparse

    import rtap_tpu.__main__ as cli

    seen = {}
    real = argparse.ArgumentParser.add_argument

    def spy(self, *names, **kw):
        if names and names[0] in ("--threshold", "--debounce") \
                and self.prog.endswith("serve"):
            seen[names[0]] = kw["default"]
        return real(self, *names, **kw)

    argparse.ArgumentParser.add_argument = spy
    try:
        with pytest.raises(SystemExit):
            cli.main(["serve", "--help"])
    finally:
        argparse.ArgumentParser.add_argument = real
    alerting = committed("configs", CONFIG)["alerting"]
    assert seen == {"--threshold": alerting["threshold"],
                    "--debounce": alerting["debounce"]}


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
    assert len(entry["why"]) <= 200
    rules.cell_entry(reg, CELL, CONFIG, TRAFFIC)
    mix, live = reg.cell(CELL)["traffic"], committed("traffic", "live-fields-1s")
    assert mix["kind"] == KIND
    for key in ("cadence_s", "phase_spread_s", "guard_s", "drain_cadences",
                "send_quantum_s", "pipeline_depth", "micro_chunk", "learn",
                "hold_until_snapshot", "null_share", "trace_window_s",
                "row_ts_base", "record"):
        assert mix[key] == live[key], key
    faults = mix["faults"]
    assert (faults["fault_node_share"], faults["fault_onset_slots"],
            faults["fault_duration_slots"]) == (0.01, "5-30", 10)
    assert sorted(faults["fault_kinds"]) == ["cpu_stress", "net_loss",
                                             "node_kill"]
    rules.reports_at_least(reg, CELL, "end_to_end",
                           {"score_p50_ms", "setup_s", "peak_bytes_per_stream"})
    # every list `node-3-live` was on holds this cell, after the cells
    # accepted before it and in their order
    assert len(JOINED) == 28
    layer = rules.reports_at_least(reg, CELL, "per_layer", JOINED | set(NEW))
    score = rules.entry(reg.manifest["end_to_end"], "score_p50_ms")
    for m in [score, *(layer[name] for name in sorted(JOINED))]:
        heads = m["workloads"][:m["workloads"].index("node-3-live") + 1]
        rules.listed_after(m["workloads"], heads, CELL)
    rules.added_in_order(reg.manifest["per_layer"], NEW, after=JOINED)
    for name, (reader, where, moves) in NEW.items():
        definition = rules.agrees_with_definition(reg, layer[name])
        assert definition["reader"] == reader
        rules.listed_after(layer[name]["workloads"], [], CELL)
        assert (layer[name]["layer"], layer[name]["moves"]) == (where, moves)


def test_manifest_lists_the_cell_on_every_list_of_the_served_node_cell():
    manifest_holds(Registry())


# ---- the offered fleet ----

FAULTS = committed("traffic", TRAFFIC)["faults"]


def test_the_offered_fleet_is_a_pure_function_of_the_seed():
    args = (1024, 50, F, 0.01, 0.5, 0.005, 504, FAULTS)
    past, sent, offered, phi, send, drawn = offered_fleet(SEED, *args)
    again = offered_fleet(SEED, *args)
    other = offered_fleet(SEED + 1, *args)
    assert past.shape == (504, 1024, F) and sent.shape == (50, 1024, F)
    assert past.dtype == sent.dtype == np.float32
    for a, b in zip((past, sent, offered, phi), again):
        assert np.array_equal(a, b, equal_nan=True)
    assert again[5] == drawn and other[5] != drawn
    assert not np.array_equal(past, other[0])
    assert 0 < phi.min() and phi.max() < 0.5 and (send >= phi).all()
    # the history carries no null and no fault; the window continues it
    assert np.isfinite(past).all()
    assert abs(float(past[-10:].mean()) - float(np.nanmean(sent[:5]))) < 5


def test_faults_are_dealt_in_the_stated_shares_and_a_killed_node_offers_nothing():
    past, sent, offered, _phi, _send, drawn = offered_fleet(
        SEED, 8192, 50, F, 0.01, 0.5, 0.005, 8, FAULTS)
    assert drawn == draw_faults(SEED, 8192, 50, FAULTS)
    assert len(drawn) == 82 and len({node for node, *_ in drawn}) == 82
    by_kind = {}
    for node, kind, t0, t1 in drawn:
        by_kind.setdefault(kind, []).append(node)
        assert 5 <= t0 <= 30 and t1 == t0 + 10
        rows = sent[t0:t1, node]
        if kind == "node_kill":
            assert not offered[t0:t1, node].any() and np.isnan(rows).all()
            assert offered[:t0, node].all() and offered[t1:, node].all()
        else:
            for field, level, sigma in FAULTS["fault_kinds"][kind]["fields"]:
                assert np.nanmax(np.abs(rows[:, field] - level)) < 6 * sigma
            assert offered[:, node].all()
    assert sorted(len(v) for v in by_kind.values()) == [27, 27, 28]
    # attempted: every slot of every node less the killed slots
    assert offered.sum() == 50 * 8192 - 10 * len(by_kind["node_kill"])
    # one null field in the stated share of the records, as live-fields-1s
    nulls = np.isnan(sent[offered]).sum(axis=1)
    assert set(np.unique(nulls)) <= {0, 1}
    assert abs(int(nulls.sum()) - 4096) <= 10 * len(by_kind["node_kill"])


def test_payloads_leave_out_what_a_killed_node_never_offered():
    faults = {**FAULTS, "fault_node_share": 0.5, "fault_onset_slots": "0-1",
              "fault_duration_slots": 2}
    _past, values, offered, _phi, _send, drawn = offered_fleet(
        SEED, 6, 4, F, 0.25, 0.3, 0.15, 8, faults)
    offsets, payloads, rows, _phi, _batch_of = build_payloads(
        SEED, 6, 4, 0.3, 0.15, 2_000_000_000, n_fields=F, null_share=0.25,
        history=8, faults=faults)
    assert any(kind == "node_kill" for _n, kind, _a, _b in drawn)
    assert rows.shape == (4, len(offsets)) and rows.sum() == offered.sum() < 24
    ids = stream_ids(6)
    for k in range(4):
        recs = [json.loads(line) for batch in payloads[k]
                for line in batch.decode().splitlines()]
        assert sorted(r["id"] for r in recs) == \
            [ids[i] for i in range(6) if offered[k, i]]
        assert rows[k].sum() == len(recs)
        for r in recs:
            got = np.array([np.nan if v is None else v for v in r["values"]],
                           np.float32)
            assert np.array_equal(got, values[k, ids.index(r["id"])],
                                  equal_nan=True)


# ---- the reference's likelihood and alert rule ----

def test_reference_likelihood_is_the_programs_over_600_ticks():
    from rtap_tpu.config import LikelihoodConfig
    from rtap_tpu.service.likelihood_batch import BatchAnomalyLikelihood

    lik = committed("configs", CONFIG)["model"]["likelihood"]
    rng = np.random.default_rng(46)
    raw = np.clip(rng.beta(2, 5, (600, 16)) + (rng.random((600, 16)) < 0.02),
                  0, 1).astype(np.float32)
    raw[:40] = np.maximum(raw[:40], 0.8)  # an untrained model's first ticks
    raw[450:462, :4] = 1.0  # a fault the rule has to see
    batch = BatchAnomalyLikelihood(LikelihoodConfig(**lik), 16)
    got = np.stack([batch.update(raw[t])[1] for t in range(600)])
    want = np.stack([ref_likelihood.log_likelihoods(raw[:, g], lik)
                     for g in range(16)], axis=1)
    np.testing.assert_allclose(want, got, rtol=0, atol=1e-9)
    # noncommittal through the probation, alive after it
    assert np.unique(want[:399]).size == 1 and want[:399].max() < 0.05
    assert want[400:].std() > 0.01 and want[455:462, :4].max() > want[:399].max()
    with pytest.raises(ValueError, match="streaming"):
        ref_likelihood.log_likelihoods(raw[:, 0], {**lik, "mode": "window"})


def test_the_alert_rule_on_a_hand_made_series():
    x = np.array([0.1, 0.6, 0.2, 0.5, 0.7, 0.9, 0.4, 0.5, 0.5, 0.5])
    rule = ref_likelihood.alerts
    assert rule(x, 0.5, 1).tolist() == [0, 1, 0, 1, 1, 1, 0, 1, 1, 1]
    assert rule(x, 0.5, 2).tolist() == [0, 0, 0, 0, 1, 1, 0, 0, 1, 1]
    assert rule(x, 0.5, 3).tolist() == [0, 0, 0, 0, 0, 1, 0, 0, 0, 1]
    # within eps of the threshold nothing is judged either way — nor the
    # ticks whose run depends on such a tick
    due, judged = ref_likelihood.judged_alerts(x, 0.5, 2, 1e-3)
    assert due.tolist() == [0, 0, 0, 0, 0, 1, 0, 0, 0, 0]
    assert judged.tolist() == [1, 1, 1, 1, 0, 1, 1, 1, 0, 0]
    due, judged = ref_likelihood.judged_alerts(x, 0.45, 2, 1e-3)
    assert judged.all() and due.tolist() == rule(x, 0.45, 2).tolist()


# ---- the new metrics through the readers the benchmark has ----

def test_the_seven_metrics_read_through_their_readers():
    reg = Registry()

    def note(name, start, dur, **args):
        return [name, start, dur, args]

    ticks = [note("rtap.loop.tick", 2_000 + 1_000_000 * k, 900_000, tick=k)
             for k in range(3)]
    alerts = [note("rtap.loop.alert", 500_000 + 1_000_000 * k, 40_000 + 10_000 * k,
                   tick=k, lines=k) for k in range(3)]
    record = {
        "bench_spans": {"warm_replay": (10.0, 61.5), "checkpoint_save": (20.0, 30.25),
                        "resume_first_tick": (100.0, 33.0)},
        "host_spans": [("checkpoint_load", 100.0 + 3 * g, 2.5) for g in range(8)]
        + [("emit", 130.0, 0.01)],
        "row_latency_ms": {"alert_line_p50": 412.5, "alert_line_p95": 640.0},
        "trace": {"window_s": 0.004},
        "scoped_planes": {"/host:CPU": {"annotations": ticks + alerts + [
            note("bench_sync", 1_000, 1_000)]}},
    }

    def read(name, rec=record):
        definition, module = reg.layer_metric(name)
        return module.read(rec, definition)

    assert read("warm_replay_s") == 61.5 and read("checkpoint_save_s") == 30.25
    assert read("checkpoint_load_s") == 20.0
    assert read("resume_first_tick_s") == 33.0
    assert read("loop_alert_ms") == pytest.approx(0.05)  # (40 + 50 + 60) us / 3
    assert read("alert_line_p50_ms.live") == 412.5
    assert read("alert_line_p95_ms.live") == 640.0
    # no alert was due: left out of the line, not 0
    quiet = {**record, "row_latency_ms": {"alert_line_p50": None,
                                         "alert_line_p95": None}}
    assert read("alert_line_p50_ms.live", quiet) is None
    # a program with no such span (the parent): nothing, never a raise
    bare = {"bench_spans": {}, "host_spans": [], "trace": None}
    for name in ("warm_replay_s", "checkpoint_save_s", "checkpoint_load_s",
                 "resume_first_tick_s", "loop_alert_ms"):
        assert read(name, bare) is None, name


# ---- the cell through benchmark.run, at a tiny fleet ----

def test_tiny_cell_resumes_serves_and_alerts_correctly(served):
    result, record = served
    assert result["correct"], result["compared"]
    H = TINY_RESUME["history_ticks"]
    _past, sent, offered, *_ = offered_fleet(
        SEED, S, N, F, 0.25, TINY_LIVE["phase_spread_s"],
        TINY_LIVE["send_quantum_s"], H,
        {**FAULTS, **TINY_FAULTS})
    assert not offered.all()  # a node was killed: its slots are not attempted
    assert result["attempted"] == offered.sum() and result["failed"] == 0
    assert set(result["metrics"]) == {"score_p50_ms", "peak_bytes_per_stream",
                                      "setup_s"}
    assert (record["scored_tick"][offered]
            == np.broadcast_to(np.arange(1, N + 1)[:, None], (N, S))[offered]).all()
    assert (record["scored_tick"][~offered] == -1).all()
    # every sampled node's whole life was compared: history, priming, window
    assert result["compared_ticks"] == H + 1 + N
    for s in record["sample"]:
        assert len(s["raw"]) == H + 1 + N and s["values"].shape == (H + 1 + N, F)
        assert np.isnan(s["values"][H]).all()  # the priming tick
        assert np.array_equal(s["values"][H + 1:], sent[:, s["stream"]],
                              equal_nan=True)
    # set-up, step by step, and the first tick after the restart
    spans = record["bench_spans"]
    assert list(spans)[2:] == [
        "traffic", "warm_replay", "checkpoint_save", "release", "state",
        "checkpoint_load", "generator_start", "warm_compile",
        "resume_first_tick"]
    loads = [d for n, _t, d in record["host_spans"] if n == "checkpoint_load"]
    assert len(loads) == 2 and sum(loads) <= spans["checkpoint_load"][1]
    assert spans["resume_first_tick"][1] > spans["checkpoint_load"][1]
    # alert lines: written while warming and after the restart, every id
    # once, none for a covered tick, and exactly the reference's for the
    # sampled nodes
    alerts = record["alerts"]
    assert alerts["lines"] > alerts["lines_after_restart"] > 0
    assert 0 < alerts["sampled_due"] <= alerts["lines"]
    assert (alerts["doubled"], alerts["for_covered_ticks"],
            alerts["sampled_wrong"]) == (0, 0, 0)
    assert record["row_latency_ms"]["alert_line_p50"] > 0
    assert record["loop_stats"]["missed_deadlines"] == 0
    assert not os.path.exists(record["checkpoint_dir"])  # ~6 GB at full size


def test_tiny_cell_under_its_u8_control_is_not_correct(root):
    control, _ = run(root, CELL, SEED, SECONDS, control=True)
    assert not control["correct"]
    assert "perm_max_frac_diff" in failed_numbers(control)


def test_a_dropped_and_a_doubled_alert_line_each_count_in_failed(root, served):
    def tamper(path, cursor):
        with open(path, "rb") as f:
            head, tail = f.read(cursor), f.read().decode().splitlines(True)
        assert len(tail) >= 2
        # the first line after the restart is lost, the last written twice
        with open(path, "wb") as f:
            f.write(head + "".join(tail[1:] + tail[-1:]).encode())

    result, record = run(root, CELL, SEED, SECONDS, hooks={"alert_sink": tamper})
    alerts, clean = record["alerts"], served[1]["alerts"]
    assert alerts["doubled"] == 1 and alerts["sampled_wrong"] == 1
    assert result["failed"] == 2 and served[0]["failed"] == 0
    assert alerts["lines"] == clean["lines"]
    # the scores and the state are what they were: `failed` alone says it
    assert result["correct"]
