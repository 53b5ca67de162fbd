"""The predictive horizon across a warm-offline restart (ISSUE 51).

docs/DEPLOYMENT.md §3 option 2 with `--predict`: a fleet is warmed through
`replay_streams(predict=k, predictor=...)`, saved, dropped, loaded back through
`resume_registry` and served by `live_loop` with a FRESH tracker, as a
restarted `serve` builds one: the paging rule's latches and the fuser's open
windows come back from the checkpoints (`PredictTracker.group_state`).
Held here, on the resident form the device path keeps its state in
(`node_preset(3)`: wide rows, [G, C, M, K*S] pools):

- the resumed fleet continues BIT-IDENTICALLY to an uninterrupted one — model
  state, the predictor-owned leaves (`pred_ring`, `pred_miss_ewma`,
  `pred_tick0`), every served predict leaf and every precursor /
  predicted_incident id;
- with `predict` at its default `replay_streams` makes the
  state tree, the scores and the checkpoint files the parent made (no
  predictor leaf, meta `predict` 0);
- the benchmark's plain reference (benchmark/reference/predict.py), the repo's
  numpy twin (`predict_update_host`) and the device reducer
  (`ops/predict_tpu.py`) agree bit for bit on seeded state at the node width;
- a resume across a horizon change is refused in words that name both
  horizons, the checkpoint and the remedy, by `resume_registry` and by
  `serve`'s usage check alike."""

import json
import os

import numpy as np
import pytest

from benchmark.reference import predict as ref_predict
from rtap_tpu.config import node_preset
from rtap_tpu.correlate import TopologyMap
from rtap_tpu.data.synthetic import LabeledStream
from rtap_tpu.models.oracle.predict import predict_update_host
from rtap_tpu.obs.health import HealthTracker
from rtap_tpu.obs.metrics import TelemetryRegistry
from rtap_tpu.predict import BlastFuser, PredictTracker
from rtap_tpu.service.loop import live_loop, replay_streams, resume_registry
from rtap_tpu.service.registry import StreamGroup, StreamGroupRegistry

CFG = node_preset(3)
G, NG, K = 4, 2, 8
S = G * NG
H, W, CHUNK = 32, 12, 8  # history ticks, served ticks, replay chunk
IDS = [f"svc{i // G:03d}-{i % G:02d}" for i in range(S)]
SPEC = {"services": {f"svc{g:03d}": IDS[g * G:(g + 1) * G] for g in range(NG)},
        "links": []}
SEED = 51
#: a rule the 44-tick life fires on both sides of the restart
RULE = dict(threshold=0.35, min_ticks=4, warmup_ticks=2, rearm_frac=0.9)


def _feed():
    """[H + W, S, 3]: a learnable level, then an unpredictable walk."""
    rng = np.random.Generator(np.random.Philox(key=(SEED, 1)))
    calm = 30 + rng.normal(0, 0.2, (H + 1, S, 3))
    wild = 10 + 60 * rng.random((W - 1, S, 3))
    vals = np.concatenate([calm, wild]).astype(np.float32)
    vals[H + 3, 1] = np.nan  # a node that sent nothing for a tick
    # two nodes the restart finds mid-story: node 2 turns wild early enough
    # to have paged (its service's window is open and it is latched at the
    # save), node 5 late enough to be two hot ticks into its run
    vals[H - 6:H + 1, 2] = 10 + 60 * rng.random((7, 3))
    vals[H - 3:H + 1, 5] = 10 + 60 * rng.random((4, 3))
    ts = 1_700_000_000 + np.arange(H + W, dtype=np.int64)
    return vals, ts


def _tracker(events):
    return PredictTracker(
        K, registry=TelemetryRegistry(), sink=events.append,
        blast=BlastFuser(TopologyMap.from_spec(SPEC), window_ticks=10,
                         seed_streams=IDS), **RULE)


def _registry(predict=K, health=True):
    reg = StreamGroupRegistry(CFG, group_size=G, backend="tpu", seed=SEED,
                              threshold=0.5, debounce=2, health=health,
                              predict=predict)
    for sid in IDS:
        reg.add_stream(sid)
    reg.finalize()
    return reg


def _streams(vals, ts, t1):
    return [LabeledStream(sid, ts[:t1], vals[:t1, i])
            for i, sid in enumerate(IDS)]


def _state(groups):
    return [{k: np.asarray(v) for k, v in g.state.items()} for g in groups]


def _uninterrupted(vals, ts):
    """The fleet stepped tick by tick with no restart -> (final states,
    served predict leaves [T, S] a key, events)."""
    events = []
    tracker = _tracker(events)
    reg = _registry()
    leaves = {k: [] for k in ("overlap", "miss_ewma", "pred_col_frac", "scored")}
    for t in range(H + W):
        row = {k: [] for k in leaves}
        for gi, grp in enumerate(reg.groups):
            lo = gi * G
            grp.collect_chunk(grp.dispatch_chunk(
                vals[t:t + 1, lo:lo + G], np.full((1, G), ts[t]), learn=True))
            tracker.fold(gi, grp.last_predict, tick=grp.ticks - 1,
                         ids=IDS[lo:lo + G])
            for k in row:
                row[k].append(grp.last_predict[k][0])
        for k in row:
            leaves[k].append(np.concatenate(row[k]))
    return _state(reg.groups), {k: np.stack(v) for k, v in leaves.items()}, events


@pytest.fixture(scope="module")
def whole():
    return _uninterrupted(*_feed())


def _event_ids(path):
    with open(path) as f:
        return [json.loads(ln)["alert_id"] for ln in f
                if ln.startswith('{"event"')
                and json.loads(ln)["event"] in ("precursor",
                                                "predicted_incident")]


def test_warm_save_drop_load_serve_is_the_uninterrupted_run(tmp_path, whole):
    vals, ts = _feed()
    want_state, want_leaves, want_events = whole
    ck, sink = str(tmp_path / "ck"), str(tmp_path / "alerts.jsonl")
    tracker = _tracker([])
    tracker.sink = None  # the loops lend the sink's writer, as serve's do
    res = replay_streams(
        _streams(vals, ts, H), CFG, backend="tpu", group_size=G,
        chunk_ticks=CHUNK, threshold=0.5, alert_path=sink, learn=True,
        checkpoint_dir=ck, checkpoint_every=H // CHUNK, debounce=2, seed=SEED,
        predict=K, predictor=tracker)
    assert tracker.sink is None  # lent for the call only
    assert res.raw.shape == (H, S) and np.isfinite(res.raw).all()
    for gi in range(NG):
        meta = json.load(open(os.path.join(ck, f"group{gi:04d}", "meta.json")))
        assert meta["predict"] == K and meta["ticks"] == H
        assert meta["alerts_offset"] <= os.path.getsize(sink)
    history_ids = _event_ids(sink)
    # a service paged in the history: its window is open in every checkpoint
    assert any(i.startswith("predicted_incident:") for i in history_ids)
    assert meta["predict_blast"]
    # the fleet that warmed is gone, its tracker with it; a fresh registry
    # and a fresh tracker, as a restarted serve builds them
    folded_before = tracker.stats()["ticks_folded"]
    assert folded_before == NG * H
    del tracker
    tracker = _tracker([])
    tracker.sink = None
    reg = _registry()
    resumed = resume_registry(reg, ck)
    assert sorted(resumed.from_ticks.values()) == [H] * NG
    for grp in reg.groups:
        assert grp.predict == K and grp.health and grp.ticks == H
        assert grp.relayouts == 1  # re-laid once, on the host, at the load
        assert np.asarray(grp.state["pred_tick0"]).tolist() == [0] * G
    served = {k: [] for k in want_leaves}
    real_fold = tracker.fold

    def fold(group, leaves, tick=-1, ids=None):
        for k in served:
            served[k].append((tick, group, np.asarray(leaves[k])[0].copy()))
        real_fold(group, leaves, tick, ids)

    tracker.fold = fold
    health = HealthTracker(CFG, registry=TelemetryRegistry())
    stats = live_loop(lambda k: (vals[H + k], int(ts[H + k])), reg, n_ticks=W,
                      cadence_s=0.005, alert_path=sink, predictor=tracker,
                      health=health, aot_warmup=True)
    assert stats["ticks"] == W and stats["predict"]["horizon_ticks"] == K
    assert [g.relayouts for g in reg.groups] == [1] * NG  # none in a tick
    assert stats["predict"]["ticks_folded"] == NG * W  # the fresh tracker's
    assert stats["health"]["groups"] == NG
    # state: every leaf of every group, the predictor's own included
    for got, want in zip(_state(reg.groups), want_state):
        assert set(got) == set(want) >= {"pred_ring", "pred_miss_ewma",
                                         "pred_tick0"}
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    # every served leaf of the window is the uninterrupted run's
    for k, rows in served.items():
        assert [t for t, _g, _v in rows] == [
            H + j for j in range(W) for _g in range(NG)]
        got = np.stack([np.concatenate([v for t, _g, v in rows if t == H + j])
                        for j in range(W)])
        np.testing.assert_array_equal(got, want_leaves[k][H:], err_msg=k)
    # and every event id, once, on both sides of the restart
    want_ids = [e["alert_id"] for e in want_events]
    got_ids = _event_ids(sink)
    # (the warm-up replays group by group where a serve interleaves them: a
    # service lies in one group, so each cluster's lines keep their order)
    assert sorted(got_ids) == sorted(want_ids)
    assert len(set(got_ids)) == len(got_ids)
    for svc in SPEC["services"]:
        assert [i for i in got_ids if svc in i] == [i for i in want_ids if svc in i]
    assert 0 < len(history_ids) < len(got_ids)
    assert any(i.startswith("predicted_incident:") for i in got_ids)
    assert {int(i.rsplit(":", 1)[1]) >= H for i in got_ids} == {True, False}


def test_with_predict_off_replay_streams_is_the_parents(tmp_path):
    """The default changes nothing: the same scores, no predictor leaf in
    the state tree or the checkpoint, meta `predict` 0 — and the scores are
    what the armed warm-up served (the reducers are pure reads)."""
    vals, ts = _feed()
    ck0, ck1 = str(tmp_path / "off"), str(tmp_path / "on")
    kw = dict(backend="tpu", group_size=G, chunk_ticks=CHUNK, threshold=0.5,
              learn=True, checkpoint_every=H // CHUNK, debounce=2, seed=SEED)
    off = replay_streams(_streams(vals, ts, H), CFG, checkpoint_dir=ck0, **kw)
    on = replay_streams(_streams(vals, ts, H), CFG, checkpoint_dir=ck1,
                        predict=K, **kw)
    np.testing.assert_array_equal(off.raw, on.raw)
    np.testing.assert_array_equal(off.log_likelihood, on.log_likelihood)
    np.testing.assert_array_equal(off.alerts, on.alerts)
    # the parent's program on the same rows: groups built with no flag at all
    bare = [StreamGroup(CFG, IDS[g * G:(g + 1) * G], seed=SEED + g,
                        backend="tpu", threshold=0.5, debounce=2)
            for g in range(NG)]
    for g, grp in enumerate(bare):
        raw = np.concatenate([
            grp.collect_chunk(grp.dispatch_chunk(
                vals[t:t + CHUNK, g * G:(g + 1) * G],
                np.tile(ts[t:t + CHUNK, None], (1, G)), learn=True))[0]
            for t in range(0, H, CHUNK)])
        np.testing.assert_array_equal(raw, off.raw[:, g * G:(g + 1) * G])
    from rtap_tpu.service.checkpoint import load_group, peek_resume_predict

    assert peek_resume_predict(ck0) == 0 and peek_resume_predict(ck1) == K
    for g, grp in enumerate(bare):
        a = load_group(os.path.join(ck0, f"group{g:04d}"))
        b = load_group(os.path.join(ck1, f"group{g:04d}"))
        assert a.predict == 0 and b.predict == K
        want = {k: np.asarray(v) for k, v in grp.state.items()}
        got = {k: np.asarray(v) for k, v in a.state.items()}
        armed = {k: np.asarray(v) for k, v in b.state.items()}
        assert set(got) == set(want) and not any(k.startswith("pred_")
                                                  for k in got)
        assert set(armed) - set(got) == {"pred_ring", "pred_miss_ewma",
                                         "pred_tick0"}
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)
            np.testing.assert_array_equal(armed[k], want[k], err_msg=k)
        meta = json.load(open(os.path.join(ck0, f"group{g:04d}", "meta.json")))
        assert meta["predict"] == 0


def test_reference_twin_and_device_reducer_agree_at_the_node_width(whole):
    """benchmark/reference/predict.py over the reference model's own state
    == predict_update_host over the program's public state == the leaves the
    fused step served, tick by tick, bit for bit (one backend)."""
    vals, ts = _feed()
    _state_end, served, _events = whole
    model = CFG.to_dict()
    for node in (0, 1, S - 1):  # node 1 has a silent tick
        ref = ref_predict.follow(model, SEED + node // G, ts, vals[:, node], K)
        for k in ("overlap", "miss_ewma", "pred_col_frac", "scored"):
            np.testing.assert_array_equal(ref[k], served[k][:, node],
                                          err_msg=f"{k} node {node}")
        assert ref["scored"][:K].sum() == 0 and ref["scored"][K:].sum() >= H
    assert not served["scored"][H + 3, 1] and np.isnan(served["overlap"][H + 3, 1])
    # the repo's numpy twin on the device's own public state, one more tick
    grp = StreamGroup(CFG, IDS[:G], seed=SEED, backend="tpu", predict=K)
    for t in range(K + 3):
        before = {k: np.asarray(grp.state[k]) for k in
                  ("pred_ring", "pred_miss_ewma", "pred_tick0")}
        grp.collect_chunk(grp.dispatch_chunk(
            vals[t:t + 1, :G], np.full((1, G), ts[t]), learn=True))
        after = {k: np.asarray(v) for k, v in grp.state.items()}
        twin_state, twin = predict_update_host({**after, **before},
                                               vals[t, :G], CFG)
        for k, v in twin.items():
            np.testing.assert_array_equal(v, grp.last_predict[k][0], err_msg=k)
        np.testing.assert_array_equal(twin_state["pred_ring"], after["pred_ring"])
        np.testing.assert_array_equal(twin_state["pred_miss_ewma"],
                                      after["pred_miss_ewma"])
    ref = ref_predict.follow(model, SEED, ts[:K + 3], vals[:K + 3, 0], K)
    np.testing.assert_array_equal(ref["pred_ring"], after["pred_ring"][0])
    np.testing.assert_array_equal(ref["pred_miss_ewma"],
                                  after["pred_miss_ewma"][0])
    # the health counts of the reference are the program's state's
    connected = ref_predict.connected_quanta(model)
    rows = {k: after[k][0] for k in ("seg_last", "presyn", "syn_perm",
                                     "prev_active", "active_seg")}
    assert ref_predict.stream_health(rows, connected) == ref["health"]


def test_a_resume_across_a_horizon_change_says_both_and_the_remedy(tmp_path):
    vals, ts = _feed()
    ck = str(tmp_path / "ck")
    replay_streams(_streams(vals, ts, CHUNK), CFG, backend="tpu", group_size=G,
                   chunk_ticks=CHUNK, checkpoint_dir=ck, checkpoint_every=1,
                   seed=SEED)  # warmed WITHOUT the predictor
    with pytest.raises(ValueError) as e:
        resume_registry(_registry(predict=K, health=False), ck)
    said = str(e.value)
    assert "horizon 0" in said and f"asks for {K}" in said and ck in said
    assert f"--predict --predict-horizon {K}" in said and "re-warm" in said
    with pytest.raises(ValueError, match="horizon"):
        replay_streams(_streams(vals, ts, 2 * CHUNK), CFG, backend="tpu",
                       group_size=G, chunk_ticks=CHUNK, checkpoint_dir=ck,
                       checkpoint_every=1, seed=SEED, predict=K)
    with pytest.raises(ValueError, match="horizon"):
        replay_streams(_streams(vals, ts, CHUNK), CFG, backend="tpu",
                       group_size=G, predict=4,
                       predictor=PredictTracker(K, registry=TelemetryRegistry()))
    # serve says the same before it makes any state
    import rtap_tpu.__main__ as cli

    rc = cli.main(["serve", "--backend", "cpu", "--streams", "a,b", "--ticks",
                   "1", "--predict", "--checkpoint-dir", ck])
    assert rc == 2
