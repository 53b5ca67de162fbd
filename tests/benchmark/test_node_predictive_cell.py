"""The cell `node-3-predictive-live` (ISSUE 51), held on the CPU: the committed
configuration `node-3-predictive` is `node-3-served`'s model key for key on
`node-3-resumed`'s bring-up, its predictive, health and alerting numbers are
`serve`'s and the trackers' own defaults, and it states its topology and its
guarantees; the manifest lists the cell on every list `node-3-resumed-live` is
on, with its seven per-layer metrics; the offered fleet is a pure function of
the seed with the cascade in the stated shape; the reference's paging rule and
fusion are the program's trackers' on hand-made series; and the cell cut to a
tiny fleet runs through the unedited harness — warm with the predictor on,
save, release, load, serve over a real socket with a fresh tracker — correct,
not correct under its u8 control, and not correct under five more controls,
each by the guarantee meant for it: three of the predictor, a reducer blind
to some lanes (the health leaf the program served), a restart that forgets
the tracker."""

import argparse
import copy
import inspect
import json
import os
import shutil

import numpy as np
import pytest

from benchmark import kernel_bytes_dense as kbd
from benchmark.generator_cascade import (
    build_payloads, draw_cascades, node_ids, offered_cascade, topology_spec)
from benchmark.reference import predict as ref_predict
from benchmark.registry import REPO, Registry
from tests.benchmark import manifest_rules as rules
from tests.benchmark.test_node_resumed_cell import JOINED as RESUMED_JOINED
from tests.benchmark.test_node_resumed_cell import NEW as RESUMED_NEW
from tests.benchmark.test_node_resumed_cell import committed
from tests.benchmark.tiny import TINY_LIVE, failed_numbers, run

CELL, CONFIG = "node-3-predictive-live", "node-3-predictive"
TRAFFIC, KIND = "live-fields-cascade-1s", "live_predictive"
SEED = 5_100_000_001  # beyond 2**31, like the driver's
SECONDS, N, F = 6.6, 6, 3  # 6 slots of 1.0 s
GROUPS, G, M = 2, 16, 8  # 2 groups x 16 nodes: 4 services of 8
S = GROUPS * G
TINY_LIKELIHOOD = {"learning_period": 24, "estimation_samples": 8}
TINY_RESUME = {"history_margin_ticks": 8, "history_ticks": 40}
#: a rule a 47-tick life exercises on both sides of the restart: a node
#: pages in the window, into a service window the history left open
TINY_PREDICTIVE = {"warmup_ticks": 2, "min_ticks": 3, "threshold": 0.15}
TINY_CASCADE = {"services": 1, "ramp_start_slots": "0-1", "ramp_slots": 2,
                "fault_slots": 2, "cascade_lag": 1}
#: the per-layer lists `node-3-resumed-live` was on when this cell joined,
#: but the TM's own share of its roofline: with the health reducer on the
#: compiler files the TM's pool sweep under the reducer's scope, so the cell
#: reports the two scopes' joint share instead (`tm_health_roofline.live`)
JOINED = (RESUMED_JOINED | set(RESUMED_NEW)) - {"tm_roofline.node.live"}
#: per-layer metrics the cell brought -> (reader, layer, moves)
NEW = {"reduce_health_ms.live": ("scope_device", "kernels", "score_p50_ms"),
       "reduce_predict_ms.live": ("scope_device", "kernels", "score_p50_ms"),
       "loop_predict_ms": ("span_sum", "serving loop", "score_p50_ms"),
       "loop_health_ms": ("span_sum", "serving loop", "score_p50_ms"),
       "tm_health_roofline.live": ("joint_roofline", "kernels",
                                   "score_p50_ms"),
       "precursor_lead_ticks.live": ("row_latency", "serving loop",
                                     "score_p50_ms"),
       "false_precursor_share.live": ("row_latency", "serving loop",
                                      "score_p50_ms")}


def make_root(tmp_path) -> str:
    """The committed benchmark under a temp root: the fleet cut to a node
    count the CPU holds in whole services, the likelihood's probation (and
    so the history) and the paging rule's waits to lengths a tiny life
    reaches, and the mix to the tiny rig's wide margins; every width, the
    record and the reducers' horizon stay."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    cfg = committed("configs", CONFIG)
    cfg["layout"].update(groups=GROUPS, group_size=G, streams=S)
    cfg["topology"].update(services=S // M, nodes_per_service=M)
    cfg["correct_sample_streams"] = 16
    cfg["model"]["likelihood"].update(TINY_LIKELIHOOD)
    cfg["resume"].update(TINY_RESUME)
    cfg["predictive"].update(TINY_PREDICTIVE)
    cfg["alerting"]["threshold"] = 0.02
    mix = committed("traffic", TRAFFIC)
    mix.update(TINY_LIVE, null_share=0.25)
    mix["cascade"].update(TINY_CASCADE)
    for sub, name, data in (("configs", CONFIG, cfg), ("traffic", TRAFFIC, mix)):
        with open(os.path.join(root, "benchmark", sub, name + ".json"), "w") as f:
            json.dump(data, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_node_predictive"))


@pytest.fixture(scope="module")
def served(root):
    """One run of the tiny cell -> (result, record)."""
    return run(root, CELL, SEED, SECONDS)


# ---- the committed files ----

def test_config_file_is_the_served_model_on_the_resumed_bring_up():
    from rtap_tpu.config import node_preset

    cfg = committed("configs", CONFIG)
    live = committed("configs", "node-3-served")
    resumed = committed("configs", "node-3-resumed")
    assert cfg["model"] == live["model"] == node_preset(3).to_dict()
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert cfg["live_cadence_s"] == 1.0
    for key in ("record", "control", "alerting"):
        assert cfg[key] == resumed[key], key
    assert {**resumed["resume"], "what": ""} == {**cfg["resume"], "what": ""}
    assert {**resumed["layout"], "note": ""} == {**cfg["layout"], "note": ""}
    assert cfg["source"] not in (live["source"], resumed["source"])
    assert len(cfg["source"]) <= 200
    kind = Registry().cell(CELL)["kind"]
    assert kind.__name__.endswith(KIND)
    # node-3-resumed's seven guarantees, none less, and the predictive ones
    assert set(cfg["guarantees"]) == set(resumed["guarantees"]) | {
        "predict", "precursors", "incidents", "health", "purity"}
    for key in resumed["guarantees"]:
        assert cfg["guarantees"][key] == resumed["guarantees"][key], key
    prec = cfg["precision"]
    assert {k: v for k, v in prec.items()
            if not k.startswith(("predict_", "health_"))} == resumed["precision"]
    assert 0 <= prec["predict_tolerance"] <= 1e-6
    assert 0 < prec["health_tolerance"] <= 1e-5
    assert set(resumed["assumed"]) | {"service_shape", "defaults"} \
        == set(cfg["assumed"])
    assert cfg["correct_sample_streams"] == 32 and "correct_ticks" not in cfg
    topo = cfg["topology"]
    assert (topo["services"], topo["nodes_per_service"], topo["links"]) \
        == (256, 32, [])
    assert topo["services"] * topo["nodes_per_service"] \
        == cfg["layout"]["streams"] == 8192
    assert cfg["layout"]["group_size"] % topo["nodes_per_service"] == 0


def test_the_predictive_and_health_numbers_are_serves_and_the_trackers_own():
    import rtap_tpu.__main__ as cli
    from rtap_tpu.obs.health import HealthTracker
    from rtap_tpu.predict import BlastFuser, PredictTracker

    cfg = committed("configs", CONFIG)
    pred, health = cfg["predictive"], cfg["health"]

    def defaults(fn):
        return {k: p.default for k, p in inspect.signature(fn).parameters.items()
                if p.default is not inspect.Parameter.empty}

    tracker, fuser = defaults(PredictTracker.__init__), defaults(BlastFuser.__init__)
    assert {k: pred[k] for k in ("threshold", "min_ticks", "warmup_ticks",
                                 "rearm_frac")} \
        == {k: tracker[k] for k in ("threshold", "min_ticks", "warmup_ticks",
                                    "rearm_frac")}
    assert pred["window_ticks"] == fuser["window_ticks"] == 256
    seen, helps = {}, {}
    real = argparse.ArgumentParser.add_argument

    def spy(self, *names, **kw):
        if names and self.prog.endswith(("serve", "replay")):
            seen[(self.prog.rsplit(" ", 1)[-1], names[0])] = kw.get("default")
            helps[(self.prog.rsplit(" ", 1)[-1], names[0])] = kw.get("help", "")
        return real(self, *names, **kw)

    argparse.ArgumentParser.add_argument = spy
    try:
        with pytest.raises(SystemExit):
            cli.main(["serve", "--help"])
    finally:
        argparse.ArgumentParser.add_argument = real
    # serve leaves the predictive knobs unset and falls back to the numbers
    # its help states — the file's
    for flag, key in (("--predict-horizon", "horizon"),
                      ("--predict-threshold", "threshold"),
                      ("--predict-min-ticks", "min_ticks")):
        assert seen[("serve", flag)] is None
        assert f"default {pred[key]}" in " ".join(helps[("serve", flag)].split())
    assert "default 8" in " ".join(helps[("replay", "--predict-horizon")].split())
    assert {k: health[k] for k in ("occupancy_threshold", "sparsity_min_frac",
                                   "drift_threshold", "drift_min_ticks")} == {
        "occupancy_threshold": seen[("serve", "--health-occupancy-threshold")],
        "sparsity_min_frac": seen[("serve", "--health-sparsity-min-frac")],
        "drift_threshold": seen[("serve", "--health-drift-threshold")],
        "drift_min_ticks": seen[("serve", "--health-drift-min-ticks")]}
    tracker = defaults(HealthTracker.__init__)
    assert all(health[k] == tracker[k] for k in (
        "occupancy_threshold", "sparsity_min_frac", "drift_threshold",
        "drift_min_ticks")) and health["on"] is True
    alerting = cfg["alerting"]
    assert (alerting["threshold"], alerting["debounce"]) == (
        seen[("serve", "--threshold")], seen[("serve", "--debounce")])


def test_state_on_the_device_is_over_a_quarter_of_the_chip():
    cfg = committed("configs", CONFIG)
    per_node = kbd.state_bytes_per_stream(cfg["model"])
    ring = cfg["predictive"]["horizon"] * cfg["model"]["sp"]["columns"] + 8
    share = cfg["layout"]["streams"] * (per_node + ring) / (16 * 2 ** 30)
    assert per_node == 760_871 and ring == 2056
    assert share >= 0.25 and share == pytest.approx(0.3638, abs=1e-4)


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
    assert mix["signal"] == {"level": 35.0, "amplitude": 20.0,
                             "period_s": 86400.0, "noise_phi": 0.9,
                             "noise_sigma": 0.3}
    cascade = mix["cascade"]
    assert {k: cascade[k] for k in cascade if k != "fault_fields"} == {
        "services": 4, "ramp_start_slots": "2-6", "ramp_slots": 24,
        "ramp_units": 16.0, "fault_slots": 10, "downstream_nodes": 3,
        "cascade_lag": 4}
    assert cascade["fault_fields"] == committed(
        "traffic", "live-fields-faults-1s")["faults"]["fault_kinds"][
            "cpu_stress"]["fields"]
    assert len(mix["cascade_tried"]) > 200
    rules.reports_at_least(reg, CELL, "end_to_end",
                           {"score_p50_ms", "setup_s", "peak_bytes_per_stream"})
    # every list `node-3-resumed-live` was on holds this cell, after the
    # cells accepted before it and in their order
    assert len(JOINED) == 34
    assert CELL not in rules.entry(reg.manifest["per_layer"],
                                   "tm_roofline.node.live")["workloads"]
    layer = rules.reports_at_least(reg, CELL, "per_layer", JOINED | set(NEW))
    score = rules.entry(reg.manifest["end_to_end"], "score_p50_ms")
    for m in [score, *(layer[name] for name in sorted(JOINED))]:
        heads = m["workloads"][:m["workloads"].index("node-3-resumed-live") + 1]
        rules.listed_after(m["workloads"], heads, CELL)
    rules.added_in_order(reg.manifest["per_layer"], NEW, after=JOINED)
    for name, (reader, where, moves) in NEW.items():
        definition = rules.agrees_with_definition(reg, layer[name])
        assert definition["reader"] == reader
        rules.listed_after(layer[name]["workloads"], [], CELL)
        assert (layer[name]["layer"], layer[name]["moves"]) == (where, moves)


def test_manifest_lists_the_cell_on_every_list_of_the_resumed_node_cell():
    manifest_holds(Registry())


# ---- the offered fleet ----

MIX = committed("traffic", TRAFFIC)
SIGNAL, CASCADE = MIX["signal"], MIX["cascade"]


def test_the_offered_fleet_is_a_pure_function_of_the_seed():
    args = (1024, 50, F, 0.01, 0.5, 0.005, 504, 32, SIGNAL, CASCADE)
    past, sent, phi, send, drawn = offered_cascade(SEED, *args)
    again = offered_cascade(SEED, *args)
    other = offered_cascade(SEED + 1, *args)
    assert past.shape == (504, 1024, F) and sent.shape == (50, 1024, F)
    assert past.dtype == sent.dtype == np.float32
    for a, b in zip((past, sent, phi), again):
        assert np.array_equal(a, b, equal_nan=True)
    assert again[4] == drawn and other[4] != drawn
    assert not np.array_equal(past, other[0])
    assert 0 < phi.min() and phi.max() < 0.5 and (send >= phi).all()
    # the history carries no null, no ramp and no fault; its noise is the
    # predictive gate's AR(1): small steps, a stationary spread of 0.69
    assert np.isfinite(past).all()
    steps = np.diff(past.astype(np.float64), axis=0)
    assert 0.25 < steps.std() < 0.4
    assert 0.55 < (past - past.mean(axis=0)).std() < 0.85
    assert 10 <= past.min() and past.max() <= 60


def test_the_cascade_has_the_stated_shape():
    past, sent, _phi, _send, drawn = offered_cascade(
        SEED, 8192, 50, F, 0.01, 0.5, 0.005, 8, 32, SIGNAL, CASCADE)
    assert drawn == draw_cascades(SEED, 8192, 50, 32, CASCADE)
    assert len(drawn) == 4 and len({c["service"] for c in drawn}) == 4
    quiet = offered_cascade(SEED, 8192, 50, F, 0.0, 0.5, 0.005, 8, 32, SIGNAL,
                            {**CASCADE, "services": 0})[1]
    touched = set()
    for c in drawn:
        r0, r1 = c["ramp"]
        assert 2 <= r0 <= 6 and r1 == r0 + 24
        assert c["origin"] // 32 == c["service"] == c["faults"][0][0] // 32
        assert len(c["faults"]) == 4
        lift = (sent[r0:r1, c["origin"]] - quiet[r0:r1, c["origin"]])
        lift = lift[np.isfinite(lift).all(axis=1)]
        assert np.allclose(lift[:, 0], lift[:, 1], atol=1e-4)
        assert 0 <= lift.min() and 14 < lift.max() < 16  # 0 -> 16 * 23/24
        for j, (node, t0, t1) in enumerate(c["faults"]):
            assert node // 32 == c["service"] and t0 == r1 + 4 * j
            assert t1 == min(50, t0 + 10)
            assert node % 32 == (c["origin"] % 32 + j) % 32
            rows = sent[t0:t1, node]
            for field, level, sigma in CASCADE["fault_fields"]:
                assert np.nanmax(np.abs(rows[:, field] - level)) < 6 * sigma
            touched.add(node)
        touched.add(c["origin"])
    assert len(touched) == 16
    # everything else is the healthy signal: the false-precursor control
    healthy = np.setdiff1d(np.arange(8192), sorted(touched))
    assert np.array_equal(sent[:, healthy], offered_cascade(
        SEED, 8192, 50, F, 0.01, 0.5, 0.005, 8, 32, SIGNAL,
        {**CASCADE, "services": 0})[1][:, healthy], equal_nan=True)
    nulls = np.isnan(sent).sum(axis=2)
    assert set(np.unique(nulls)) <= {0, 1} and nulls.sum() == 4096


def test_ids_spec_and_payloads_name_a_node_for_its_service():
    from rtap_tpu.correlate import TopologyMap

    ids = node_ids(8192, 32)
    assert (ids[0], ids[31], ids[32], ids[-1]) == (
        "svc000-00", "svc000-31", "svc001-00", "svc255-31")
    spec = topology_spec(8192, 32)
    assert len(spec["services"]) == 256 and spec["links"] == []
    assert spec["services"]["svc007"] == ids[7 * 32:8 * 32]
    topo = TopologyMap.from_spec(spec)
    assert topo.node_of("svc007-03") == "svc007-03"  # dotless: its own node
    assert topo.cluster_of("svc007-03") == "svc007" != topo.cluster_of(ids[0])
    _past, values, _phi, _send, _drawn = offered_cascade(
        SEED, 16, 3, F, 0.25, 0.3, 0.15, 8, 8, SIGNAL,
        {**CASCADE, **TINY_CASCADE})
    offsets, payloads, rows, _phi, _batch = build_payloads(
        SEED, 16, 3, 0.3, 0.15, 2_000_000_000, n_fields=F, null_share=0.25,
        history=8, nodes_per_service=8, signal=SIGNAL,
        cascade={**CASCADE, **TINY_CASCADE})
    assert rows.sum() == 16 and len(rows) == len(offsets)
    ids = node_ids(16, 8)
    for k in range(3):
        recs = [json.loads(line) for batch in payloads[k]
                for line in batch.decode().splitlines()]
        assert sorted(r["id"] for r in recs) == ids
        for r in recs:
            got = np.array([np.nan if v is None else v for v in r["values"]],
                           np.float32)
            assert np.array_equal(got, values[k, ids.index(r["id"])],
                                  equal_nan=True)
            assert r["ts"] == 2_000_000_000 + k


def test_the_joint_share_is_the_tms_floor_over_both_scopes_time():
    """The TM's bytes already hold every leaf the health reducer reads: the
    pools are read once for both, so the joint floor is the TM's alone."""
    model = committed("configs", CONFIG)["model"]
    leaves = kbd.leaf_bytes(model)
    read, _written = kbd.KERNELS["rtap.tm"]
    assert {"seg_last", "presyn", "syn_perm", "prev_active", "active_seg"} \
        <= set(read)
    assert sum(leaves[k] for k in ("seg_last", "presyn", "syn_perm",
                                   "prev_active", "active_seg")) == 436_224
    assert kbd.kernel_bytes_per_stream("rtap.tm", model) == 534_784
    definition, module = Registry().layer_metric("tm_health_roofline.live")
    assert definition["scopes"] == ["rtap.tm", "rtap.reduce.health"]
    assert definition["floor"] == "rtap.tm"
    record = {"trace": {"window_s": 1.0}, "chunk_ticks": 1,
              "config": committed("configs", CONFIG),
              "device_kind": "TPU v5 lite",
              "scoped_planes": {"/host:CPU": {"annotations": [
                  ["bench_sync", 1_000, 1_000, {}]]}},
              "scope_tables": {"jit_chunk_step": {
                  "rtap.tm.learn": 7.0, "rtap.tm.dendrite": 1.5,
                  "rtap.tm.activate": 0.5, "rtap.reduce.health": 3.0,
                  "rtap.sp.learn": 1.0}}}
    floor = kbd.kernel_floor_seconds("rtap.tm", model, 1024, "TPU v5 lite")
    assert floor == pytest.approx(534_784 * 1024 / 819e9)
    assert module.read(record, definition) == pytest.approx(
        100 * floor / 12.0e-3)
    # wherever the compiler files the sweep, the share does not move
    moved = copy.deepcopy(record)
    moved["scope_tables"]["jit_chunk_step"].update(
        {"rtap.tm.dendrite": 3.3, "rtap.reduce.health": 1.2})
    assert module.read(moved, definition) == pytest.approx(
        module.read(record, definition))


# ---- the reference's paging rule and fusion against the trackers ----

def test_the_paging_rule_is_the_trackers_on_made_trajectories():
    from rtap_tpu.obs.metrics import TelemetryRegistry
    from rtap_tpu.predict import PredictTracker

    rng = np.random.default_rng(51)
    T, n = 300, 24
    scored = rng.random((T, n)) < 0.9
    scored[:8] = False
    ewma = np.clip(0.3 + 0.25 * np.sin(np.arange(T)[:, None] / 11.0
                                        + rng.uniform(0, 6, n))
                   + rng.normal(0, 0.03, (T, n)), 0, 1).astype(np.float32)
    ewma[:20, :4] = np.nan  # a node whose first scored tick comes late
    for kw in ({}, {"min_ticks": 3, "warmup_ticks": 8},
               {"threshold": 0.45, "rearm_frac": 0.8}):
        events = []
        tracker = PredictTracker(8, registry=TelemetryRegistry(),
                                 sink=events.append, **kw)
        ids = [f"n{i}" for i in range(n)]
        for t0 in range(0, T, 4):  # chunks of four ticks, as a replay folds
            tracker.fold(0, {"scored": scored[t0:t0 + 4],
                             "miss_ewma": ewma[t0:t0 + 4],
                             "overlap": 1 - ewma[t0:t0 + 4],
                             "pred_col_frac": ewma[t0:t0 + 4]},
                         tick=t0 + 3, ids=ids)
        got = {(e["stream"], e["tick"]) for e in events}
        rule = {"threshold": 0.35, "min_ticks": 12, "warmup_ticks": 32,
                "rearm_frac": 0.5, **kw}
        want = {(ids[i], t) for i in range(n)
                for t in ref_predict.precursor_ticks(scored[:, i], ewma[:, i],
                                                     **rule)}
        assert got == want and len(got) == len(events) > 0, kw
        assert tracker.streams_scored == scored.sum()


def test_the_fusion_is_the_blast_fusers_on_a_made_list():
    from rtap_tpu.correlate import TopologyMap
    from rtap_tpu.predict import BlastFuser

    spec = topology_spec(24, 8)
    fuser = BlastFuser(TopologyMap.from_spec(spec), window_ticks=20,
                       seed_streams=node_ids(24, 8))
    made = [("svc000-03", 5), ("svc000-01", 9), ("svc001-02", 9),
            ("svc000-05", 29), ("svc000-07", 50), ("svc002-00", 51),
            ("svc001-02", 60)]
    got = [fuser.precursor(node, tick, {"alert_id": f"precursor:{node}:{tick}"})
           for node, tick in made]
    got = [(e["cluster"], e["tick"], e["first_node"], frozenset(e["blast_radius"]))
           for e in got if e is not None]
    cluster_of = {n: svc for svc, nodes in spec["services"].items() for n in nodes}
    want = ref_predict.fuse(made, cluster_of.__getitem__, spec["services"], 20)
    assert got == [(i["cluster"], i["tick"], i["first_node"], i["blast_radius"])
                   for i in want]
    # 29 is within 20 of 9 (attached); 50 is 21 past 29 (a new window)
    assert [(c, t) for c, t, _n, _r in got] == [
        ("svc000", 5), ("svc001", 9), ("svc000", 50), ("svc002", 51),
        ("svc001", 60)]
    assert all(len(r) == 8 for _c, _t, _n, r in got)


# ---- the new metrics through their readers ----

def test_the_seven_metrics_read_through_their_readers():
    reg = Registry()

    def note(name, start, dur, **args):
        return [name, start, dur, args]

    ticks = [note("rtap.loop.tick", 2_000 + 1_000_000 * k, 900_000, tick=k)
             for k in range(3)]
    folds = [note("rtap.loop.predict", 500_000 + 1_000_000 * k,
                  400_000 + 100_000 * k, tick=k, precursors=k, incidents=0)
             for k in range(3)] + [
        note("rtap.loop.health", 400_000 + 1_000_000 * k, 30_000, tick=k)
        for k in range(3)]
    cfg = committed("configs", CONFIG)
    record = {
        "row_latency_ms": {"precursor_lead_ticks": 7.5,
                           "false_precursor_share": 0.25},
        "trace": {"window_s": 0.004}, "chunk_ticks": 1, "config": cfg,
        "device_kind": "TPU v5 lite",
        "scoped_planes": {"/host:CPU": {"annotations": ticks + folds + [
            note("bench_sync", 1_000, 1_000)]}},
        "scope_tables": {"jit_chunk_step": {
            "rtap.reduce.health": 1.25, "rtap.reduce.predict": 0.04,
            "rtap.tm.learn": 7.5}},
    }

    def read(name, rec=record):
        definition, module = reg.layer_metric(name)
        return module.read(rec, definition)

    assert read("reduce_health_ms.live") == 1.25
    assert read("reduce_predict_ms.live") == 0.04
    assert read("loop_predict_ms") == pytest.approx(0.5)  # (.4 + .5 + .6) / 3
    assert read("loop_health_ms") == pytest.approx(0.03)
    assert read("tm_health_roofline.live") == pytest.approx(
        100 * (534_784 * 1024 / 819e9) / (1.25e-3 + 7.5e-3))
    assert read("precursor_lead_ticks.live") == 7.5
    assert read("false_precursor_share.live") == 0.25
    # a run that fired nothing: left out of the line, not 0
    quiet = {**record, "row_latency_ms": {"precursor_lead_ticks": None,
                                         "false_precursor_share": None}}
    assert read("precursor_lead_ticks.live", quiet) is None
    assert read("false_precursor_share.live", quiet) is None
    # a step whose reducers are off carries no such scope: 0 ms, no share
    off = {**record, "scope_tables": {"jit_chunk_step": {"rtap.tm.learn": 7.5}}}
    assert read("reduce_health_ms.live", off) == 0.0
    assert read("tm_health_roofline.live", off) is None
    # a program with no such span or trace (the parent): nothing, no raise
    bare = {"trace": None}
    for name in ("reduce_health_ms.live", "reduce_predict_ms.live",
                 "loop_predict_ms", "loop_health_ms",
                 "tm_health_roofline.live"):
        assert read(name, bare) is None, name


# ---- the cell through benchmark.run, at a tiny fleet ----

def tiny_mix():
    return {**CASCADE, **TINY_CASCADE}


def test_tiny_cell_warms_resumes_and_pages_correctly(served):
    result, record = served
    assert result["correct"], (result["compared"], record["predictive"])
    H = TINY_RESUME["history_ticks"]
    _past, sent, _phi, _send, drawn = offered_cascade(
        SEED, S, N, F, 0.25, TINY_LIVE["phase_spread_s"],
        TINY_LIVE["send_quantum_s"], H, M, SIGNAL, tiny_mix())
    assert result["attempted"] == N * S and result["failed"] == 0
    assert set(result["metrics"]) == {"score_p50_ms", "peak_bytes_per_stream",
                                      "setup_s"}
    assert (record["scored_tick"]
            == np.broadcast_to(np.arange(1, N + 1)[:, None], (N, S))).all()
    assert result["compared_ticks"] == H + 1 + N
    origin = drawn[0]["origin"]
    assert origin in {s["stream"] for s in record["sample"]}
    for s in record["sample"]:
        assert len(s["raw"]) == H + 1 + N
        assert np.array_equal(s["values"][H + 1:], sent[:, s["stream"]],
                              equal_nan=True)
    spans = record["bench_spans"]
    assert list(spans)[2:] == [
        "traffic", "warm_replay", "checkpoint_save", "release", "state",
        "checkpoint_load", "generator_start", "warm_compile",
        "resume_first_tick"]
    # the predictive guarantees: every one held, and not vacuously
    p = record["predictive"]
    assert (p["predict_max_abs_diff"], p["predict_bits_wrong"],
            p["folds_lost"], p["health_counts_wrong"]) == (0.0, 0, 0, 0)
    assert (p["precursors_wrong"], p["incidents_wrong"], p["doubled"],
            p["for_covered_ticks"]) == (0, 0, 0, 0)
    assert p["sampled_precursors_due"] > 0 and p["event_lines"] > 0
    # ... on both sides of the restart: the serving tracker is a fresh one,
    # and what it pages attaches to the windows the checkpoints carry
    assert 0 < p["event_lines_after_restart"] < p["event_lines"]
    # the served health leaf of the cascade's group, and not an empty one
    tol = committed("configs", CONFIG)["precision"]["health_tolerance"]
    assert p["health_leaf_wrong"] == 0
    assert p["health_leaf_max_abs_diff"] <= tol
    assert p["health_leaf_group"] == origin // G
    assert p["health_leaf_tick"] == N and 0 < p["health_leaf_live"] <= G
    leaf = p["health_leaf"]
    assert sum(leaf["occ_hist"]) == p["health_leaf_live"]
    assert leaf["syn_frac"] > 0 and leaf["act_col_frac"] > 0
    assert sum(leaf["perm_hist"]) == pytest.approx(1.0, abs=1e-5)
    # the loop folded with both trackers and the correlator armed
    stats = record["loop_stats"]
    assert stats["predict"]["ticks_folded"] == GROUPS * (1 + N)
    assert stats["predict"]["horizon_ticks"] == 8
    assert stats["health"] and "incidents" in stats
    assert stats["missed_deadlines"] == 0
    assert {"predict", "health"} <= {n for n, _t, _d in record["host_spans"]}
    assert not os.path.exists(record["checkpoint_dir"])


def test_tiny_cell_under_its_u8_control_is_not_correct(root):
    control, record = run(root, CELL, SEED, SECONDS, control=True)
    assert not control["correct"]
    assert "perm_max_frac_diff" in failed_numbers(control)


def only_rows_misrouted(result):
    assert not result["correct"]
    assert failed_numbers(result) == {"rows_misrouted"}


def test_a_ring_read_one_slot_off_fails_the_predict_guarantee(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    from rtap_tpu.ops import predict_tpu

    real = predict_tpu.predict_update

    def slipped(state, values, cfg):
        off = {**state, "pred_ring": jnp.roll(state["pred_ring"], 1, axis=1)}
        wrong, leaf = real(off, values, cfg)  # scored against slot t%k - 1
        right, _ = real(state, values, cfg)   # the ring written in place
        return {**right, "pred_miss_ewma": wrong["pred_miss_ewma"]}, leaf

    monkeypatch.setattr(predict_tpu, "predict_update", slipped)
    jax.clear_caches()
    try:
        result, record = run(root, CELL, SEED, SECONDS)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    only_rows_misrouted(result)
    p = record["predictive"]
    assert p["predict_max_abs_diff"] > 1e-6
    # the lines follow the served leaves: the rule and the fusion still hold
    assert (p["precursors_wrong"], p["incidents_wrong"], p["doubled"]) == (0, 0, 0)
    assert result["failed"] == 0


def test_a_reducer_blind_to_some_lanes_fails_the_health_guarantee(
        root, monkeypatch):
    """The planted fault ISSUE 51 fears: a change to the TM's resident rows
    leaves the health reducer reading a quarter of each row's lanes as
    empty. Scores, state, predict leaves and lines are untouched; the leaf
    the program serves is not the state's."""
    import jax

    from rtap_tpu.ops import health_tpu, step

    real = health_tpu.health_reduce

    def blind(state, raw, values, cfg):
        presyn = state["presyn"]
        n = presyn.shape[-1] // 4
        # (the first lanes: a young model's segments grow from lane 0)
        return real({**state, "presyn": presyn.at[..., :n].set(-1)},
                    raw, values, cfg)

    for mod in (health_tpu, step):
        if hasattr(mod, "health_reduce"):
            monkeypatch.setattr(mod, "health_reduce", blind)
    jax.clear_caches()
    try:
        result, record = run(root, CELL, SEED, SECONDS)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    only_rows_misrouted(result)
    p = record["predictive"]
    tol = committed("configs", CONFIG)["precision"]["health_tolerance"]
    assert p["health_leaf_wrong"] > 0 and p["health_leaf_max_abs_diff"] > tol
    assert (p["predict_max_abs_diff"], p["predict_bits_wrong"],
            p["health_counts_wrong"], p["precursors_wrong"],
            p["incidents_wrong"], p["doubled"]) == (0.0, 0, 0, 0, 0, 0)
    assert result["failed"] == 0


def test_a_restart_that_forgets_the_tracker_pages_a_service_twice(
        root, monkeypatch):
    """The serving tracker is a fresh one, as a restarted process builds it:
    were the checkpoints not to carry the old one's latches and open
    windows, a service the history paged would be paged again in the
    window."""
    from rtap_tpu.predict import PredictTracker

    monkeypatch.setattr(PredictTracker, "restore_group",
                        lambda self, group, state: None)
    result, record = run(root, CELL, SEED, SECONDS)
    only_rows_misrouted(result)
    p = record["predictive"]
    assert p["precursors_wrong"] + p["incidents_wrong"] > 0
    assert p["event_lines_after_restart"] > 0
    assert (p["predict_max_abs_diff"], p["predict_bits_wrong"],
            p["health_leaf_wrong"]) == (0.0, 0, 0)


def test_another_min_ticks_in_the_program_fails_the_precursors_guarantee(root):
    result, record = run(root, CELL, SEED, SECONDS,
                         hooks={"tracker_kw": {"min_ticks": 2}})
    only_rows_misrouted(result)
    p = record["predictive"]
    assert p["precursors_wrong"] > 0
    assert (p["predict_max_abs_diff"], p["predict_bits_wrong"],
            p["health_counts_wrong"], p["incidents_wrong"]) == (0.0, 0, 0, 0)
    assert result["failed"] == p["precursors_wrong"]


def test_a_radius_missing_a_declared_node_fails_the_incidents_guarantee(
        root, served):
    spec = copy.deepcopy(topology_spec(S, M))
    # a sibling the program's map never hears of: the service pages without it
    paged = sorted({line for line in served[1]["predictive"]["paged_clusters"]})
    assert paged
    dropped = spec["services"][paged[0]].pop()
    result, record = run(root, CELL, SEED, SECONDS,
                         hooks={"topology_spec": spec})
    only_rows_misrouted(result)
    p = record["predictive"]
    assert p["incidents_wrong"] > 0 and dropped not in paged
    assert (p["predict_max_abs_diff"], p["predict_bits_wrong"],
            p["precursors_wrong"]) == (0.0, 0, 0)
    assert result["failed"] == p["incidents_wrong"]
