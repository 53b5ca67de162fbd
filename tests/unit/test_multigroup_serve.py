"""Multi-group live serving (service/loop.py live_loop over a registry).

Measured chip throughput peaks at small G (SCALING.md bench G-sweep), so
at-scale serving is many interleaved groups per chip. These tests pin the
registry path of live_loop: per-group slicing of the source vector, NaN
padding of the sealed partial group, dispatch-all-then-collect-all
ordering, alert emission only for live slots — and bit-exact equivalence
of a registry group against the same streams served as one standalone
group (same seed, same feed => same final model state).
"""

import json

import numpy as np

from rtap_tpu.config import cluster_preset
from rtap_tpu.service.loop import live_loop
from rtap_tpu.service.registry import StreamGroup, StreamGroupRegistry

G_TOTAL = 6
GROUP_SIZE = 4  # -> groups of [4 live, 2 live + 2 pad]
IDS = [f"s{i}" for i in range(G_TOTAL)]
N_TICKS = 12


def _feed(k: int):
    rng = np.random.Generator(np.random.Philox(key=(11, k)))
    return (30 + 5 * rng.random(G_TOTAL)).astype(np.float32), 1_700_000_000 + k


def _registry():
    reg = StreamGroupRegistry(cluster_preset(), group_size=GROUP_SIZE,
                              backend="tpu")
    for sid in IDS:
        reg.add_stream(sid)
    reg.finalize()
    return reg


def _alert_records(path):
    """The ALERT records of a shared alert/event stream file. Watchdog
    events (rtap_tpu.obs; json.dumps puts their discriminating "event" key
    first) carry wall-clock measurements, so they are legitimately
    nondeterministic across otherwise bit-identical runs — bitexactness is
    a contract on the alert stream, not on latency telemetry."""
    with open(path) as f:
        return "".join(l for l in f if not l.startswith('{"event"'))


def test_registry_live_loop_stats_and_alert_hygiene(tmp_path):
    reg = _registry()
    assert [g.n_live for g in reg.groups] == [4, 2]
    path = str(tmp_path / "alerts.jsonl")
    stats = live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.01,
                      alert_path=path)
    assert stats["scored"] == G_TOTAL * N_TICKS  # live slots only, no pads
    assert stats["n_groups"] == 2
    assert stats["ticks"] == N_TICKS
    for line in open(path):
        rec = json.loads(line)
        if "event" in rec:
            # watchdog events (rtap_tpu.obs) share the alert stream,
            # discriminated by their "event" key — never alert-shaped
            assert "stream" not in rec
            continue
        assert not rec["stream"].startswith("__pad")


def test_registry_group_bitexact_vs_standalone():
    """Group 0 of the registry must evolve bit-identically to a standalone
    StreamGroup over the same 4 streams and feed (same seed, same kernel
    path): the multi-group schedule may not perturb the model math."""
    reg = _registry()
    live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.01)

    solo = StreamGroup(cluster_preset(), IDS[:GROUP_SIZE], backend="tpu")
    for k in range(N_TICKS):
        values, ts = _feed(k)
        solo.run_chunk(values[None, :GROUP_SIZE],
                       np.full((1, GROUP_SIZE), ts, np.int64))

    a, b = reg.groups[0].state, solo.state
    assert set(a) == set(b)
    for key in a:
        np.testing.assert_array_equal(
            np.asarray(a[key]), np.asarray(b[key]), err_msg=key)


def test_unfinalized_registry_rejected_loudly():
    import pytest

    reg = StreamGroupRegistry(cluster_preset(), group_size=GROUP_SIZE,
                              backend="tpu")
    for sid in IDS:
        reg.add_stream(sid)  # 6 streams, group_size 4: 2 left pending
    with pytest.raises(ValueError, match="finalize"):
        live_loop(_feed, reg, n_ticks=1, cadence_s=0.01)


def test_source_length_mismatch_rejected_loudly():
    import pytest

    reg = _registry()
    bad = lambda k: (np.zeros(G_TOTAL - 1, np.float32), 1_700_000_000)  # noqa: E731
    with pytest.raises(ValueError, match="live streams"):
        live_loop(bad, reg, n_ticks=1, cadence_s=0.01)


def test_multifield_source_through_registry():
    """[G, n_fields] sources (node_preset multivariate) must survive the
    padding path — StreamGroup.tick always supported them."""
    from rtap_tpu.config import node_preset

    reg = StreamGroupRegistry(node_preset(n_metrics=2), group_size=2,
                              backend="tpu")
    for sid in ("n0", "n1", "n2"):
        reg.add_stream(sid)
    reg.finalize()

    def feed(k):
        rng = np.random.Generator(np.random.Philox(key=(13, k)))
        return (30 + rng.random((3, 2))).astype(np.float32), 1_700_000_000 + k

    stats = live_loop(feed, reg, n_ticks=4, cadence_s=0.01)
    assert stats["scored"] == 3 * 4 and stats["n_groups"] == 2


def test_live_checkpoint_resume_bitexact(tmp_path):
    """A serve killed and restarted from its checkpoint dir must continue
    bit-identically to an uninterrupted serve: 6 ticks + resume + 6 ticks
    == 12 ticks, state-for-state, across both groups (incl. the padded
    one). SURVEY.md §5 checkpoint/resume at the live-service level."""
    ck = str(tmp_path / "ck")

    # uninterrupted reference
    ref = _registry()
    live_loop(_feed, ref, n_ticks=12, cadence_s=0.01)

    # first serve: 6 ticks, checkpoint every 2 (last save lands on tick 6)
    first = _registry()
    stats1 = live_loop(_feed, first, n_ticks=6, cadence_s=0.01,
                       checkpoint_dir=ck, checkpoint_every=2)
    assert stats1["checkpoints_saved"] == 3

    # "restart": fresh registry, same ids/config, resumes from the dir and
    # continues with the rest of the feed
    second = _registry()
    stats2 = live_loop(lambda k: _feed(k + 6), second, n_ticks=6,
                       cadence_s=0.01, checkpoint_dir=ck)
    assert stats2["resumed_from"] == {"group0": 6, "group1": 6}

    for gi in range(2):
        a, b = second.groups[gi].state, ref.groups[gi].state
        assert set(a) == set(b)
        for key in a:
            np.testing.assert_array_equal(
                np.asarray(a[key]), np.asarray(b[key]), err_msg=f"g{gi}/{key}")


def test_torn_checkpoint_set_resumes_with_skew(tmp_path):
    """A crash between per-group saves leaves groups at different ticks.
    Live data is not tick-indexed and groups are independent, so the serve
    must come back up (a behind group merely lost some learning) — with
    the skew surfaced in stats, not hidden."""
    import shutil

    ck = str(tmp_path / "ck")
    first = _registry()
    live_loop(_feed, first, n_ticks=4, cadence_s=0.01,
              checkpoint_dir=ck, checkpoint_every=2)
    shutil.rmtree(ck + "/group0001")  # group1's save "lost in the crash"
    stats = live_loop(_feed, _registry(), n_ticks=2, cadence_s=0.01,
                      checkpoint_dir=ck)
    assert stats["resumed_from"] == {"group0": 4}  # group1 started fresh
    assert stats["resume_tick_skew"] == 4
    assert stats["scored"] == G_TOTAL * 2


def test_checkpoint_requires_registry(tmp_path):
    import pytest

    grp = StreamGroup(cluster_preset(), IDS, backend="tpu")
    with pytest.raises(ValueError, match="Registry"):
        live_loop(_feed, grp, n_ticks=1, cadence_s=0.01,
                  checkpoint_dir=str(tmp_path))


def test_graceful_stop_saves_final_state(tmp_path):
    """An orderly shutdown (stop_event, serve's SIGTERM path) finishes the
    current tick, saves final state, and reports truncated-but-honest
    stats instead of dying silently."""
    import threading

    ck = str(tmp_path / "ck")
    reg = _registry()
    stop = threading.Event()

    def feed_then_stop(k):
        if k == 3:
            stop.set()  # raised mid-run, e.g. by a signal handler
        return _feed(k)

    stats = live_loop(feed_then_stop, reg, n_ticks=50, cadence_s=0.01,
                      checkpoint_dir=ck, checkpoint_every=10,
                      stop_event=stop)
    assert stats["stopped_early"] is True
    assert stats["ticks"] == 4 and stats["ticks_requested"] == 50
    assert stats["scored"] == G_TOTAL * 4
    assert stats["checkpoints_saved"] == 1  # the final on-stop save

    # the saved state resumes exactly where the stop landed
    cont = _registry()
    stats2 = live_loop(lambda k: _feed(k + 4), cont, n_ticks=1,
                       cadence_s=0.01, checkpoint_dir=ck)
    assert stats2["resumed_from"] == {"group0": 4, "group1": 4}


def test_single_group_path_unchanged(tmp_path):
    """A bare StreamGroup still works through live_loop (the pre-registry
    API), and emits for every slot."""
    grp = StreamGroup(cluster_preset(), IDS, backend="tpu")
    stats = live_loop(_feed, grp, n_ticks=5, cadence_s=0.01,
                      alert_path=str(tmp_path / "a.jsonl"))
    assert stats["scored"] == G_TOTAL * 5
    assert stats["n_groups"] == 1


def test_pipeline_depth2_bitexact_vs_depth1(tmp_path):
    """pipeline_depth=2 changes WHEN results are collected (one tick
    later), never WHAT is computed: alert lines, throughput, and final
    model state must be bit-identical to depth 1 — including across a
    mid-run checkpoint save, which drains the pipeline first."""
    out = {}
    for depth in (1, 2):
        reg = _registry()
        path = str(tmp_path / f"alerts_d{depth}.jsonl")
        ck = str(tmp_path / f"ck_d{depth}")
        stats = live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.0,
                          alert_path=path, checkpoint_dir=ck,
                          checkpoint_every=5, pipeline_depth=depth)
        assert stats["pipeline_depth"] == depth
        assert stats["scored"] == G_TOTAL * N_TICKS
        import jax

        out[depth] = (_alert_records(path),
                      [jax.tree_util.tree_map(lambda x: np.asarray(x).copy(),
                                              g.state) for g in reg.groups],
                      stats["checkpoints_saved"])
    assert out[1][0] == out[2][0]  # identical alert stream, same order
    for s1, s2 in zip(out[1][1], out[2][1]):
        l1 = jax.tree_util.tree_leaves(s1)
        l2 = jax.tree_util.tree_leaves(s2)
        assert len(l1) == len(l2)
        for a, b in zip(l1, l2):
            np.testing.assert_array_equal(a, b)
    assert out[1][2] == out[2][2]


def test_pipeline_depth_validation():
    import pytest

    reg = _registry()
    with pytest.raises(ValueError, match="pipeline_depth"):
        live_loop(_feed, reg, n_ticks=2, cadence_s=0.0, pipeline_depth=0)


def test_dispatch_threads_bitexact_vs_serial(tmp_path):
    """dispatch_threads=N overlaps the per-group dispatch/collect calls
    (the serial ~65 ms/group floor of a chip that is not host-local, which
    depth-2 pipelining alone cannot touch —
    reports/live_soak_pipelined.json); it must never change
    WHAT is computed: alert stream, order, and final model state are
    bit-identical to serial dispatch, including across a mid-run
    checkpoint drain and with depth 2 stacked on top."""
    import jax

    out = {}
    for threads in (1, 4):
        reg = _registry()
        path = str(tmp_path / f"alerts_t{threads}.jsonl")
        ck = str(tmp_path / f"ck_t{threads}")
        stats = live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.0,
                          alert_path=path, checkpoint_dir=ck,
                          checkpoint_every=5, pipeline_depth=2,
                          dispatch_threads=threads)
        # stats carry the EFFECTIVE worker count (capped at n_groups; 1
        # when the pool was never created), not the requested flag value
        assert stats["dispatch_threads"] == min(threads, len(reg.groups))
        assert stats["scored"] == G_TOTAL * N_TICKS
        out[threads] = (_alert_records(path),
                        [jax.tree_util.tree_map(
                            lambda x: np.asarray(x).copy(), g.state)
                         for g in reg.groups])
    assert out[1][0] == out[4][0]  # identical alert stream, same order
    for s1, s2 in zip(out[1][1], out[4][1]):
        l1, l2 = jax.tree_util.tree_leaves(s1), jax.tree_util.tree_leaves(s2)
        assert len(l1) == len(l2)
        for a, b in zip(l1, l2):
            np.testing.assert_array_equal(a, b)


def test_dispatch_threads_validation():
    import pytest

    reg = _registry()
    with pytest.raises(ValueError, match="dispatch_threads"):
        live_loop(_feed, reg, n_ticks=2, cadence_s=0.0, dispatch_threads=0)


# What --freeze freezes: the learned tensors. Everything else is temporal
# context that inference itself evolves (NuPIC TM with learn=False still
# computes activations and predictions; it just never touches permanences,
# synapse growth, or duty cycles). seg_pot is dynamic: it is the count of
# potential synapses whose presynaptic cell fired at the PREVIOUS step —
# frozen weights x evolving activity (models/state.py).
FROZEN_KEYS = {"perm", "syn_perm", "presyn", "members", "boost",
               "active_duty", "overlap_duty", "seg_last", "tm_overflow",
               "sp_iter", "enc_bound", "enc_offset", "enc_resolution"}
DYNAMIC_KEYS = {"active_seg", "matching_seg", "prev_active", "prev_winner",
                "seg_pot", "tm_iter"}


def test_freeze_serves_without_mutating_learned_state(tmp_path):
    """learn=False (serve --freeze, NuPIC disableLearning parity): every
    learned tensor (SP permanences/boost/duty cycles, TM synapses/pools)
    is bit-identical after any number of frozen ticks, while scoring
    still flows, temporal context still evolves, and the host-side
    likelihood normalizer keeps adapting."""
    reg = _registry()
    # mature the models first: a frozen fresh model only proves zeros
    live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.0)
    before = [{k: np.asarray(v).copy() for k, v in g.state.items()}
              for g in reg.groups]
    assert FROZEN_KEYS | DYNAMIC_KEYS == set(before[0])  # no key unaccounted
    lik_records_before = [g.likelihood.records for g in reg.groups]

    path = str(tmp_path / "alerts_frozen.jsonl")
    ck = tmp_path / "ck_frozen"
    ck.mkdir()
    stats = live_loop(lambda k: _feed(k + N_TICKS), reg, n_ticks=N_TICKS,
                      cadence_s=0.0, alert_path=path, learn=False,
                      checkpoint_dir=str(ck), checkpoint_every=3)
    assert stats["learn"] is False
    assert stats["scored"] == G_TOTAL * N_TICKS  # scoring still flows
    # frozen serving treats --checkpoint-dir as strictly read-only: no
    # periodic saves, no exit save (replicas may share a golden dir)
    assert stats["checkpoints_saved"] == 0
    assert list(ck.iterdir()) == []
    # the likelihood normalizer is downstream of the model and must keep
    # adapting while frozen (documented --freeze semantics)
    for n0, g in zip(lik_records_before, reg.groups):
        assert g.likelihood.records == n0 + N_TICKS

    for b, g in zip(before, reg.groups):
        for key in FROZEN_KEYS:
            np.testing.assert_array_equal(
                b[key], np.asarray(g.state[key]), err_msg=key)
        # the recurrent context must still advance — a frozen model that
        # stops predicting would score every tick anomalous
        assert any(not np.array_equal(b[k], np.asarray(g.state[k]))
                   for k in DYNAMIC_KEYS)


def test_micro_chunk_bitexact_vs_per_tick(tmp_path):
    """micro_chunk=M batches M ticks into one dispatch (the per-program-
    floor amortizer, SCALING.md round 5): alert lines, throughput, and
    final model state must be bit-identical to per-tick dispatch — the
    chunked scan IS the same program the per-tick path runs, including a
    non-divisible tail (N_TICKS=12, M=5 -> chunks 5+5+2) and composed
    with depth 2 + threads."""
    import jax

    out = {}
    for m in (1, 5):
        reg = _registry()
        path = str(tmp_path / f"alerts_m{m}.jsonl")
        stats = live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.0,
                          alert_path=path, pipeline_depth=2,
                          dispatch_threads=2, micro_chunk=m)
        assert stats["micro_chunk"] == m
        assert stats["scored"] == G_TOTAL * N_TICKS
        out[m] = (_alert_records(path),
                  [jax.tree_util.tree_map(lambda x: np.asarray(x).copy(),
                                          g.state) for g in reg.groups])
    assert out[1][0] == out[5][0]  # identical alert stream, same order
    for s1, s2 in zip(out[1][1], out[5][1]):
        for a, b in zip(jax.tree_util.tree_leaves(s1),
                        jax.tree_util.tree_leaves(s2)):
            np.testing.assert_array_equal(a, b)


def test_micro_chunk_validation_and_stagger_stats():
    import pytest

    reg = _registry()
    with pytest.raises(ValueError, match="micro_chunk"):
        live_loop(_feed, reg, n_ticks=2, cadence_s=0.0, micro_chunk=0)


def test_micro_chunk_early_stop_flushes_buffer(tmp_path):
    """A stop_event landing mid-chunk must still score the buffered ticks
    (nothing ingested is silently dropped)."""
    import threading

    reg = _registry()
    stop = threading.Event()
    calls = [0]

    def feed(k):
        calls[0] += 1
        if calls[0] == 8:  # mid-chunk for M=5 (ticks 6..8 buffered)
            stop.set()
        return _feed(k)

    path = str(tmp_path / "alerts_stop.jsonl")
    stats = live_loop(feed, reg, n_ticks=N_TICKS, cadence_s=0.0,
                      alert_path=path, micro_chunk=5, stop_event=stop)
    # stop is checked at the TOP of the next tick: 8 ticks were polled,
    # all 8 must be scored (5 in the first chunk, 3 flushed)
    assert stats["ticks"] == 8
    assert stats["scored"] == G_TOTAL * 8


def test_chunk_stagger_content_equal_and_state_bitexact(tmp_path):
    """chunk_stagger rotates WHEN each group's chunk dispatches, never WHAT
    any group computes: final model state must be bit-identical to plain
    per-tick serving, and the alert stream must contain exactly the same
    lines (order differs across groups by design — per stream it is still
    chronological)."""
    import jax

    out = {}
    for mode in ("plain", "stagger"):
        reg = _registry()
        path = str(tmp_path / f"alerts_{mode}.jsonl")
        kw = dict(micro_chunk=3, chunk_stagger=True) if mode == "stagger" \
            else {}
        stats = live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.0,
                          alert_path=path, pipeline_depth=2,
                          dispatch_threads=2, **kw)
        assert stats["scored"] == G_TOTAL * N_TICKS
        out[mode] = (sorted(_alert_records(path).splitlines()),
                     [jax.tree_util.tree_map(lambda x: np.asarray(x).copy(),
                                             g.state) for g in reg.groups])
    assert out["plain"][0] == out["stagger"][0]
    for s1, s2 in zip(out["plain"][1], out["stagger"][1]):
        for a, b in zip(jax.tree_util.tree_leaves(s1),
                        jax.tree_util.tree_leaves(s2)):
            np.testing.assert_array_equal(a, b)


def test_chunk_stagger_validation():
    import pytest

    reg = _registry()
    with pytest.raises(ValueError, match="micro_chunk >= 2"):
        live_loop(_feed, reg, n_ticks=2, cadence_s=0.0, chunk_stagger=True)


def test_chunk_stagger_checkpoint_resume_bitexact(tmp_path):
    """Periodic checkpoints under chunk_stagger force a boundary
    realignment; the saved state matches the last emitted tick exactly,
    so resume continues bit-identically to an uninterrupted plain run
    (chunking never changes WHAT is computed)."""
    ck = str(tmp_path / "ck")

    ref = _registry()
    live_loop(_feed, ref, n_ticks=12, cadence_s=0.01)

    first = _registry()
    stats1 = live_loop(_feed, first, n_ticks=6, cadence_s=0.01,
                       checkpoint_dir=ck, checkpoint_every=4,
                       micro_chunk=3, chunk_stagger=True)
    assert stats1["checkpoints_saved"] >= 1

    second = _registry()
    stats2 = live_loop(lambda k: _feed(k + 6), second, n_ticks=6,
                       cadence_s=0.01, checkpoint_dir=ck,
                       micro_chunk=3, chunk_stagger=True)
    assert stats2["resumed_from"] == {"group0": 6, "group1": 6}
    for gi in range(2):
        a, b = second.groups[gi].state, ref.groups[gi].state
        for key in a:
            np.testing.assert_array_equal(
                np.asarray(a[key]), np.asarray(b[key]), err_msg=f"g{gi}/{key}")


def test_micro_chunk_checkpoint_cadence_not_degraded(tmp_path):
    """checkpoint_every that is no multiple of micro_chunk must still save
    at every first boundary PAST due (due-since-last-save trigger), not at
    lcm(M, checkpoint_every): M=4, every=3 over 12 ticks -> saves at
    boundaries 4, 8, 12 (three), where the old modulus rule saved only at
    tick 12."""
    reg = _registry()
    ck = str(tmp_path / "ck")
    stats = live_loop(_feed, reg, n_ticks=N_TICKS, cadence_s=0.0,
                      checkpoint_dir=ck, checkpoint_every=3, micro_chunk=4)
    assert stats["checkpoints_saved"] == 3


def test_live_checkpoint_resume_with_micro_chunk(tmp_path):
    """Resume composes with micro_chunk: a serve chunking M=3 ticks per
    dispatch, killed after its tick-6 checkpoint, restarted with the same
    M, must continue bit-identically to an uninterrupted M=3 serve (saves
    land only at chunk boundaries; the due-since trigger keeps the
    cadence)."""
    ck = str(tmp_path / "ck")

    ref = _registry()
    live_loop(_feed, ref, n_ticks=12, cadence_s=0.01, micro_chunk=3)

    first = _registry()
    stats1 = live_loop(_feed, first, n_ticks=6, cadence_s=0.01,
                       checkpoint_dir=ck, checkpoint_every=2, micro_chunk=3)
    # boundaries at 3, 6: due-since-last >= 2 fires at both
    assert stats1["checkpoints_saved"] == 2

    second = _registry()
    stats2 = live_loop(lambda k: _feed(k + 6), second, n_ticks=6,
                       cadence_s=0.01, checkpoint_dir=ck, micro_chunk=3)
    assert stats2["resumed_from"] == {"group0": 6, "group1": 6}

    for gi in range(2):
        a, b = second.groups[gi].state, ref.groups[gi].state
        for key in a:
            np.testing.assert_array_equal(
                np.asarray(a[key]), np.asarray(b[key]), err_msg=f"g{gi}/{key}")
