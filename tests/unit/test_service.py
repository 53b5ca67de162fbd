"""Service-layer tests: batched likelihood parity, groups, replay loop."""

import json

import numpy as np
import pytest

from rtap_tpu.config import LikelihoodConfig, cluster_preset
from rtap_tpu.data.synthetic import SyntheticStreamConfig, generate_cluster
from rtap_tpu.models.oracle.likelihood import AnomalyLikelihood
from rtap_tpu.service.likelihood_batch import BatchAnomalyLikelihood
from rtap_tpu.service.loop import live_loop, replay_streams
from rtap_tpu.service.registry import StreamGroup, StreamGroupRegistry


def _scores(n, g, seed=0):
    rng = np.random.Generator(np.random.Philox(key=(seed, 2)))
    s = rng.random((n, g)) * 0.3
    s[n // 2 :, :] *= 0.5
    s[int(n * 0.8), :] = 1.0  # a spike
    return s


@pytest.mark.parametrize("mode", ["window", "streaming"])
def test_batch_likelihood_matches_oracle(mode):
    cfg = LikelihoodConfig(mode=mode, learning_period=40, estimation_samples=20,
                           historic_window_size=120, reestimation_period=10)
    G, N = 5, 300
    batch = BatchAnomalyLikelihood(cfg, G)
    oracles = [AnomalyLikelihood(cfg) for _ in range(G)]
    scores = _scores(N, G)
    for i in range(N):
        lik_b, log_b = batch.update(scores[i])
        for g in range(G):
            lik_o, log_o = oracles[g].update(float(scores[i, g]))
            # batch reductions may differ from sequential sums by ~ulps
            assert lik_b[g] == pytest.approx(lik_o, rel=1e-9, abs=1e-12), f"step {i} g {g}"
            assert log_b[g] == pytest.approx(log_o, rel=1e-9, abs=1e-12), f"step {i} g {g}"


@pytest.mark.parametrize("mode", ["window", "streaming"])
def test_batch_likelihood_checkpoint_roundtrip(mode):
    cfg = LikelihoodConfig(mode=mode, learning_period=30, estimation_samples=10,
                           historic_window_size=80, reestimation_period=10)
    G, N = 3, 150
    a = BatchAnomalyLikelihood(cfg, G)
    scores = _scores(N, G, seed=3)
    for i in range(N // 2):
        a.update(scores[i])
    b = BatchAnomalyLikelihood(cfg, G)
    b.load_state_dict({k: np.copy(v) for k, v in a.state_dict().items()})
    for i in range(N // 2, N):
        la, ga = a.update(scores[i])
        lb, gb = b.update(scores[i])
        np.testing.assert_array_equal(la, lb)
        np.testing.assert_array_equal(ga, gb)


def test_group_backends_agree():
    """TPU group tick == CPU oracle group tick, end to end with likelihood."""
    cfg = cluster_preset()
    ids = [f"s{i}" for i in range(3)]
    tpu = StreamGroup(cfg, ids, backend="tpu")
    cpu = StreamGroup(cfg, ids, backend="cpu")
    rng = np.random.Generator(np.random.Philox(key=(1, 4)))
    for i in range(120):
        v = (40 + 10 * rng.random(3)).astype(np.float32)
        if i == 90:
            v[1] += 60
        rt = tpu.tick(v, 1_700_000_000 + i)
        rc = cpu.tick(v, 1_700_000_000 + i)
        np.testing.assert_allclose(rt.raw, rc.raw, atol=0)  # bit-exact on CPU platform
        np.testing.assert_allclose(rt.log_likelihood, rc.log_likelihood, rtol=1e-9)


def test_chunk_matches_ticks():
    """run_chunk(T ticks) == T sequential tick() calls."""
    cfg = cluster_preset()
    ids = [f"s{i}" for i in range(4)]
    a = StreamGroup(cfg, ids, backend="tpu")
    b = StreamGroup(cfg, ids, backend="tpu")
    rng = np.random.Generator(np.random.Philox(key=(2, 4)))
    T = 60
    vals = (30 + 5 * rng.random((T, 4))).astype(np.float32)
    ts = (1_700_000_000 + np.arange(T)[:, None] + np.zeros((1, 4))).astype(np.int64)
    raw_chunk, ll_chunk, _ = a.run_chunk(vals, ts)
    for i in range(T):
        res = b.tick(vals[i], ts[i])
        np.testing.assert_array_equal(raw_chunk[i], res.raw, err_msg=f"tick {i}")
        np.testing.assert_array_equal(ll_chunk[i], res.log_likelihood, err_msg=f"tick {i}")


def test_registry_grouping_and_padding():
    cfg = cluster_preset()
    reg = StreamGroupRegistry(cfg, group_size=4, backend="cpu")
    for i in range(6):
        reg.add_stream(f"node{i}.cpu")
    reg.finalize()
    assert len(reg.groups) == 2
    assert reg.groups[0].n_live == 4 and reg.groups[1].n_live == 2
    assert reg.groups[1].G == 4  # padded to fixed size
    grp, slot = reg.lookup("node4.cpu")
    assert grp is reg.groups[1] and slot == 0
    with pytest.raises(KeyError):
        reg.add_stream("node0.cpu")


def test_replay_streams_end_to_end(tmp_path):
    """Replay a small synthetic cluster; anomalies raise scores; alerts JSONL."""
    scfg = SyntheticStreamConfig(length=500, cadence_s=1.0, n_anomalies=1,
                                 kinds=("spike",), anomaly_magnitude=8.0)
    streams = generate_cluster(3, metrics=("cpu",), cfg=scfg, seed=5)
    cfg = cluster_preset()
    path = str(tmp_path / "alerts.jsonl")
    res = replay_streams(streams, cfg, backend="tpu", group_size=2,
                         chunk_ticks=50, alert_path=path)
    assert res.raw.shape == (500, 3)
    assert res.throughput["scored"] == 1500
    # segment-pool headroom rides beside tm_overflow_total (500 ticks of a
    # 2-slot pool: cells fill, and the stats say so)
    assert res.throughput["tm_overflow_total"] == 0
    assert 0 < res.throughput["tm_max_segments"] <= cfg.tm.max_segments_per_cell
    assert res.throughput["tm_full_cells"] >= res.throughput["tm_full_columns"] >= 0
    # every line in the alert file is valid JSON with the expected keys
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == res.throughput["alerts"] == int(res.alerts.sum())
    for l in lines[:3]:
        assert set(l) == {"stream", "ts", "value", "raw_score", "log_likelihood"}


def test_live_loop_paced():
    cfg = cluster_preset()
    grp = StreamGroup(cfg, [f"s{i}" for i in range(4)], backend="tpu")
    rng = np.random.Generator(np.random.Philox(key=(3, 4)))

    def source(k):
        return (30 + 5 * rng.random(4)).astype(np.float32), 1_700_000_000 + k

    import time as _time

    t0 = _time.perf_counter()
    stats = live_loop(source, grp, n_ticks=6, cadence_s=0.25)
    elapsed = _time.perf_counter() - t0
    assert stats["scored"] == 24 and stats["ticks"] == 6
    # This pins PACING SEMANTICS, not performance: the loop must sleep off
    # unused budget (so 6 ticks take >= 5 cadences) and count only genuine
    # overruns. The cadence is deliberately generous — at 0.02 s this test
    # flaked whenever a background process stole the 1-core host for a few
    # ticks (observed: 4/10 missed under a concurrent jax-init probe).
    assert elapsed >= 5 * 0.25
    assert stats["missed_deadlines"] <= 2  # first tick compiles; allow jitter


def test_learn_false_freezes_state():
    """Inference-only stepping must not mutate learned state on either backend."""
    import jax

    cfg = cluster_preset()
    ids = [f"s{i}" for i in range(3)]
    rng = np.random.Generator(np.random.Philox(key=(9, 4)))
    warm = (30 + 5 * rng.random((40, 3))).astype(np.float32)
    probe = (30 + 5 * rng.random((10, 3))).astype(np.float32)
    ts0 = 1_700_000_000

    for backend in ("tpu", "cpu"):
        grp = StreamGroup(cfg, ids, backend=backend)
        for i in range(40):
            grp.tick(warm[i], ts0 + i)
        if backend == "tpu":
            before = {k: np.asarray(v) for k, v in jax.device_get(grp.state).items()}
        else:
            before = [{k: np.copy(v) for k, v in s.items()} for s in grp._states]
        for i in range(10):
            grp.tick(probe[i], ts0 + 40 + i, learn=False)
        # learned state identical; only the recurrent activity /iter slots move
        frozen = ("perm", "syn_perm", "presyn", "boost", "overlap_duty",
                  "active_duty", "seg_last", "sp_iter")
        if backend == "tpu":
            after = {k: np.asarray(v) for k, v in jax.device_get(grp.state).items()}
            for k in frozen:
                np.testing.assert_array_equal(before[k], after[k], err_msg=f"{backend}:{k}")
        else:
            for g in range(3):
                for k in frozen:
                    np.testing.assert_array_equal(
                        before[g][k], grp._states[g][k], err_msg=f"{backend}:{k}"
                    )


def test_replay_learn_false_runs():
    scfg = SyntheticStreamConfig(length=120, cadence_s=1.0, n_anomalies=0)
    streams = generate_cluster(2, metrics=("cpu",), cfg=scfg, seed=6)
    cfg = cluster_preset()
    res = replay_streams(streams, cfg, backend="tpu", chunk_ticks=40, learn=False)
    assert res.raw.shape == (120, 2) and np.isfinite(res.raw).all()


# ---- advisor-finding guards (round 5) ----


def test_bulk_add_rejects_pad_prefix():
    """A pad-prefixed id on the PRE-finalize bulk path must fail like
    claim_slot's guard: buffered, it would silently read as pad capacity
    (never emitted) and its slot could later be double-claimed."""
    reg = StreamGroupRegistry(cluster_preset(), group_size=4, backend="tpu")
    with pytest.raises(ValueError, match="__pad"):
        reg.add_stream("__pad_evil")


def test_live_loop_rejects_unfinalized_registry():
    """Exact-multiple stream counts leave _pending empty WITHOUT finalize();
    live_loop must still refuse — post-finalize membership (claims/releases)
    on an unfinalized registry buffers into _pending, invisible to the
    loop's groups snapshot."""
    reg = StreamGroupRegistry(cluster_preset(), group_size=2, backend="tpu")
    reg.add_stream("a")
    reg.add_stream("b")  # seals the group: _pending is empty, not finalized
    assert not reg._pending and not reg._finalized

    def source(k):
        return np.zeros(2, np.float32), 1_700_000_000 + k

    with pytest.raises(ValueError, match="finalize"):
        live_loop(source, reg, n_ticks=1, cadence_s=0.01)


def test_stray_checkpoint_guard_matches_long_group_names(tmp_path):
    """group indices >= 10000 are saved as 'group10000' (5 digits); the
    stray-topology scan must catch them too, not just \\d{4}."""
    import os

    reg = StreamGroupRegistry(cluster_preset(), group_size=2, backend="tpu")
    reg.add_stream("a")
    reg.finalize()
    os.makedirs(tmp_path / "group10000")

    def source(k):
        return np.zeros(1, np.float32), 1_700_000_000 + k

    with pytest.raises(ValueError, match="beyond this"):
        live_loop(source, reg, n_ticks=1, cadence_s=0.01,
                  checkpoint_dir=str(tmp_path), checkpoint_every=1)


def test_frozen_replay_from_completed_checkpoint_errors(tmp_path):
    """Resuming a COMPLETED run's final checkpoint (frozen or learning)
    would silently score zero ticks; it must error and point at
    serve --freeze."""
    scfg = SyntheticStreamConfig(length=64, cadence_s=1.0, n_anomalies=0)
    streams = generate_cluster(1, metrics=("cpu",), cfg=scfg, seed=6)
    cfg = cluster_preset()
    ck = str(tmp_path / "ck")
    replay_streams(streams, cfg, backend="tpu", chunk_ticks=32,
                   checkpoint_dir=ck, checkpoint_every=1)
    with pytest.raises(ValueError, match="nothing left to replay"):
        replay_streams(streams, cfg, backend="tpu", chunk_ticks=32,
                       checkpoint_dir=ck, learn=False)
    # same silent no-op exists for a LEARNING replay resumed at the end
    with pytest.raises(ValueError, match="nothing left to replay"):
        replay_streams(streams, cfg, backend="tpu", chunk_ticks=32,
                       checkpoint_dir=ck)


def test_partial_multigroup_resume_still_works(tmp_path):
    """The all-complete guard must NOT break crash recovery when only SOME
    groups finished: a completed group skips (all-NaN rows, prior-run
    semantics) while the interrupted group replays to the end."""
    import shutil

    scfg = SyntheticStreamConfig(length=64, cadence_s=1.0, n_anomalies=0)
    streams = generate_cluster(2, metrics=("cpu",), cfg=scfg, seed=6)
    cfg = cluster_preset()
    ck = str(tmp_path / "ck")
    replay_streams(streams, cfg, backend="tpu", group_size=1, chunk_ticks=32,
                   checkpoint_dir=ck, checkpoint_every=1)
    # simulate a crash that lost group1's checkpoint: group0 is complete,
    # group1 must restart from scratch — the replay must run, not raise
    shutil.rmtree(tmp_path / "ck" / "group0001")
    res = replay_streams(streams, cfg, backend="tpu", group_size=1,
                         chunk_ticks=32, checkpoint_dir=ck)
    assert np.isnan(res.raw[:, 0]).all()      # completed group: prior run's
    assert np.isfinite(res.raw[:, 1]).all()   # interrupted group: rescored
    assert res.throughput["resumed_from"] == {"group0": 64}


@pytest.mark.quick
def test_occupancy_sums_over_every_local_device(monkeypatch):
    """Regression for the ISSUE 15 device-scope finding: the stats line's
    occupancy read local_devices()[0] only, under-reporting HBM by the shard
    count on a multi-device host. It must SUM bytes over the local device
    list (and stay numerically identical on single-device hosts)."""
    import jax

    from rtap_tpu.service.loop import _device_stats

    class _Grp:
        backend = "tpu"

    def _occupancy():
        return {k: v for k, v in _device_stats([_Grp()]).items()
                if k.startswith("hbm_")}

    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    devs = [_Dev({"bytes_in_use": 100, "peak_bytes_in_use": 150}),
            _Dev({"bytes_in_use": 40, "peak_bytes_in_use": 60}),
            _Dev(None)]  # a backend exposing no stats must not poison
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    out = _occupancy()
    assert out == {"hbm_bytes_in_use": 140, "hbm_peak_bytes_in_use": 210}
    # single-device: identical to the old [0] read
    monkeypatch.setattr(jax, "local_devices", lambda: devs[:1])
    assert _occupancy() == {"hbm_bytes_in_use": 100,
                            "hbm_peak_bytes_in_use": 150}
