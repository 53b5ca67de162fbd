"""`correct` takes the row as it was fed, over a stated number of ticks.

On the CPU at tiny sizes: a three-field configuration goes through the
replay kind and is correct, and is not under its lower-precision control or
with two fields swapped in the fed row; the one-field feed is the parent's
to the byte; a configuration that states `correct_ticks` is compared over
those ticks and against the state AT that tick however long the window ran,
with the read's pause in the record and out of the clock."""

import hashlib
import time

import numpy as np
import pytest

from benchmark import check, program
from benchmark.reference.model import ReferenceStream
from benchmark.registry import Registry
from benchmark.traffic_kinds.replay import (GroupFeed, in_flight_limit,
                                            slowest_interval, trace_budget_s)
from tests.benchmark import tiny_nab, tiny_node
from tests.benchmark.tiny import failed_numbers

SEED = 4_320_000_001  # beyond 2**31, like the driver's


# ---- the feed ----

# sha256 of GroupFeed(seed, group, G, T).values(chunk) / .ts(chunk), written
# from the parent's code (7401b08) before GroupFeed learnt of fields
PARENT_CHUNKS = [
    ((4_100_000_001, 0, 8, 8), 0, "d755eacf8e451260", "5f7ed3e31d68a7f1"),
    ((4_100_000_001, 1, 8, 8), 3, "4f9d37a1f7551a45", "d274316925d3d5af"),
    ((3_100_000_101, 5, 1024, 8), 47, "250db9e8dc1dcc6a", "ec1fe2515b4f829a"),
    ((7, 0, 17, 8), 16, "e6e6e550ca582a8c", "2ec79c25bb908666"),
]


def _sha(a: np.ndarray) -> str:
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("args,chunk,values_sha,ts_sha", PARENT_CHUNKS)
def test_one_field_feed_is_the_parents_to_the_byte(args, chunk, values_sha,
                                                   ts_sha):
    feed = GroupFeed(*args)
    v = feed.values(chunk)
    assert v.shape == (args[3], args[2]) and v.dtype == np.float32
    assert (_sha(v), _sha(feed.ts(chunk))) == (values_sha, ts_sha)
    # ... and is field 0 of the same group's multi-field feed
    assert _sha(np.ascontiguousarray(
        GroupFeed(*args, n_fields=3).values(chunk)[..., 0])) == values_sha


def test_three_fields_are_three_signals_and_repeat_per_seed():
    a = GroupFeed(SEED, 1, 8, 8, n_fields=3)
    v = a.values(2)
    assert v.shape == (8, 8, 3) and v.dtype == np.float32
    assert a.ts(2).shape == (8, 8)
    for f, g in ((0, 1), (0, 2), (1, 2)):
        # own noise lane and own per-stream phase: no field repeats another
        assert np.abs(v[..., f] - v[..., g]).mean() > 1.0
        assert (a.phases[f] != a.phases[g]).any()
    assert np.array_equal(v, GroupFeed(SEED, 1, 8, 8, n_fields=3).values(2))
    assert not np.array_equal(
        v, GroupFeed(SEED + 1, 1, 8, 8, n_fields=3).values(2))
    assert not np.array_equal(v, a.values(3))
    assert not np.array_equal(
        v, GroupFeed(SEED, 0, 8, 8, n_fields=3).values(2))


@pytest.mark.parametrize("cell,chunk_s,limit,traced_s", [
    # two rounds of its 128 groups, 23 chunks of them in flight at the start
    ("cluster-32-replay", 0.011, 24, 256 * 0.011),
    ("cluster-256-replay", 0.0815, 24, 10.0),  # 123 chunks fit the 10 s
    ("nab-2048-replay", 1.09, 2, 10.0)])       # 9 chunks
def test_a_traced_window_holds_no_more_chunks_than_the_mix_states(
        cell, chunk_s, limit, traced_s):
    c = Registry().cell(cell)
    traffic = c["traffic"]
    assert (traffic["trace_window_s"], traffic["trace_max_chunks"]) == (10.0, 256)
    # a group is pipeline_depth deep, all groups together dispatch_ahead_chunks
    assert in_flight_limit(traffic, c["config"]["layout"]["groups"]) == limit
    assert in_flight_limit({"pipeline_depth": 2}, 128) == 2
    elapsed = 40.0
    done = round(elapsed / chunk_s)
    # the profiler starts this long before the close; what is in flight then
    # runs inside the traced window as well, which ends at the last collect
    budget = trace_budget_s(traffic, 50.0, elapsed, done, limit - 1)
    assert budget + (limit - 1) * chunk_s == pytest.approx(traced_s, rel=1e-3)
    assert budget > 1.0
    assert trace_budget_s(traffic, 50.0, elapsed, done) \
        == pytest.approx(traced_s, rel=1e-3)
    # a window shorter than the mix's is traced whole, from its first chunk
    assert trace_budget_s(traffic, 1.5, 0.0, 0) == 1.5
    del traffic["trace_max_chunks"]
    assert trace_budget_s(traffic, 50.0, elapsed, 10_000) == 10.0


def test_the_slowest_interval_tells_a_stopped_host_from_a_slow_device():
    # chunks done at 1, 2, 3; at 5 after a host that stopped (the device had
    # long finished: no wait); at 8 after a pause off the clock and a wait
    spans = [("collect_wait", 0.1, 0.9), ("collect_wait", 1.1, 0.9),
             ("collect_wait", 2.1, 0.9), ("collect_wait", 5.0, 0.0),
             ("correct_pause", 5.1, 0.5), ("collect_wait", 7.5, 0.5)]
    assert slowest_interval(spans) == pytest.approx(
        {"slowest_interval_s": 3.0 - 0.5, "median_interval_s": 1.5,
         "slowest_interval_wait_s": 0.5, "long_intervals": 0,
         "long_intervals_lost_s": 0.0})
    assert slowest_interval(spans[:4]) == pytest.approx(
        {"slowest_interval_s": 2.0, "median_interval_s": 1.0,
         "slowest_interval_wait_s": 0.0, "long_intervals": 0,
         "long_intervals_lost_s": 0.0})
    assert slowest_interval(spans[:2]) == {}


# ---- a three-field configuration through the replay kind ----

@pytest.fixture(scope="module")
def node_root(tmp_path_factory):
    return tiny_node.make_root(tmp_path_factory.mktemp("bench_node"))


def test_three_field_cell_is_correct_and_its_control_is_not(node_root):
    sound, record = tiny_node.run(node_root, SEED, 1.0)
    assert sound["correct"], sound["compared"]
    assert record["groups_stepped"] == 2 and sound["failed"] == 0
    s = record["sample"][0]
    assert s["values"].shape == (len(s["raw"]), 3)  # the row as it was fed
    assert sound["compared_ticks"] == len(s["raw"])
    cfg = program.model_config(record["config"])
    assert cfg.n_fields == 3 and not cfg.sp.sparse_pool
    control, _ = tiny_node.run(node_root, SEED, 1.0, control=True)
    assert not control["correct"]
    assert "perm_max_frac_diff" in failed_numbers(control)


def test_two_fields_swapped_in_the_fed_row_is_not_correct(node_root,
                                                          monkeypatch):
    from rtap_tpu.service.registry import StreamGroup

    inner = StreamGroup.dispatch_chunk

    def dispatch_chunk(self, values, ts, learn=True):
        return inner(self, np.asarray(values)[..., [1, 0, 2]], ts, learn=learn)

    monkeypatch.setattr(StreamGroup, "dispatch_chunk", dispatch_chunk)
    result, _ = tiny_node.run(node_root, SEED + 1, 1.0)
    assert not result["correct"]
    assert failed_numbers(result) & {"raw_max_abs_diff", "perm_max_frac_diff"}


@pytest.mark.parametrize("n_fields,row", [(3, (2,)), (3, ()), (1, (3,))])
def test_a_row_of_the_wrong_length_fails_loudly(node_root, n_fields, row):
    config = Registry(node_root).cell(tiny_node.CELL)["config"]
    config["model"]["n_fields"] = n_fields
    sample = [{"stream": 5, "seed": 1, "ts": np.arange(4),
               "values": np.ones((4,) + row, np.float32),
               "raw": np.zeros(4, np.float32)}]
    with pytest.raises(ValueError, match=r"stream 5: fed rows of shape .* "
                                         rf"takes {n_fields} field"):
        check.compare(config, sample, 0, 0)


def test_the_reference_refuses_only_what_it_lacks(node_root):
    from benchmark.reference.config import ModelConfig

    model = Registry(node_root).cell(tiny_node.CELL)["config"]["model"]
    ReferenceStream(ModelConfig.from_dict(model), 1)  # three fields: taken
    model["classifier"]["enabled"] = True
    with pytest.raises(ValueError, match="no SDR classifier, no learning "
                                         "cadence and no delta field"):
        ReferenceStream(ModelConfig.from_dict(model), 1)


# ---- a stated number of followed ticks ----

FOLLOW = 16  # two chunks of the tiny NAB twin


@pytest.fixture(scope="module")
def root16(tmp_path_factory):
    return tiny_nab.make_root(tmp_path_factory.mktemp("bench_follow"),
                              correct_ticks=FOLLOW)


def test_followed_ticks_do_not_grow_with_the_window(root16, monkeypatch):
    """`compared_ticks` is the stated 16 however long the window ran, and
    the reference does the same work — counted in calls, which is what
    `reference_s` is made of and, unlike a time, repeats under load."""
    calls = []
    inner = ReferenceStream.run

    def counted(self, ts_unix, value):
        calls.append(ts_unix)
        return inner(self, ts_unix, value)

    monkeypatch.setattr(ReferenceStream, "run", counted)
    followed = []
    for seconds in (0.75, 1.5):
        calls.clear()
        result, record = tiny_nab.run(root16, SEED, seconds)
        assert result["correct"], result["compared"]
        assert result["compared_ticks"] == FOLLOW
        assert result["reference_s"] > 0
        assert len(calls) == FOLLOW * len(record["sample"])
        assert all(len(s["raw"]) == FOLLOW for s in record["sample"])
        followed.append((record["n_chunks"], len(calls)))
    assert followed[1][0] > followed[0][0] > FOLLOW // 8  # the window grew
    assert followed[1][1] == followed[0][1]               # the reference not
    # the whole group's segment counter, after the window
    definition, reader = Registry().layer_metric("tm_full_cells.nab")
    assert reader.read(record, definition) == 0
    assert record["tm_capacity"]["max_segments_on_a_cell"] >= 1


@pytest.mark.parametrize("learns_until,correct", [(FOLLOW, True), (8, False)])
def test_the_state_compared_is_that_of_the_stated_tick(root16, monkeypatch,
                                                       learns_until, correct):
    """A timed path that stops learning after tick 16 (its state from then
    on returned unchanged) is still correct — nothing after tick 16 is
    compared; one that stops after tick 8 is not."""
    import jax
    import jax.numpy as jnp

    from rtap_tpu.service.registry import StreamGroup

    inner = StreamGroup.dispatch_chunk

    def dispatch_chunk(self, values, ts, learn=True):
        # copied from the first chunk on, so that nothing compiles later
        kept = jax.tree.map(jnp.copy, self.state)
        fed = getattr(self, "_fed", 0)
        handle = inner(self, values, ts, learn=learn)
        if fed >= learns_until:
            self.state = kept
        self._fed = fed + len(values)
        return handle

    monkeypatch.setattr(StreamGroup, "dispatch_chunk", dispatch_chunk)
    result, record = tiny_nab.run(root16, SEED + 2, 1.0)
    assert record["n_chunks"] > FOLLOW // 8 and result["compared_ticks"] == FOLLOW
    assert result["correct"] is correct, result["compared"]
    assert ("perm_max_frac_diff" in failed_numbers(result)) is not correct


def test_the_pause_is_in_the_record_and_out_of_the_clock(root16, monkeypatch):
    inner = program.state_rows

    def slow_rows(group, slot, leaves):
        time.sleep(0.1)
        return inner(group, slot, leaves)

    monkeypatch.setattr(program, "state_rows", slow_rows)
    seconds = 1.0
    result, record = tiny_nab.run(root16, SEED + 3, seconds)
    assert result["correct"], result["compared"]
    n = len(record["sample"])
    assert record["paused_s"] >= 0.1 * n
    (pause,) = [s for s in record["host_spans"] if s[0] == "correct_pause"]
    assert pause[2] == record["paused_s"]
    t_first, t_last = record["window"]
    assert t_first < pause[1] and pause[1] + pause[2] < t_last
    stepping = t_last - t_first - record["paused_s"]
    assert seconds <= stepping < t_last - t_first
    assert result["metrics"]["metrics_per_s"]["value"] == \
        record["rows_scored"] / stepping


def test_a_window_short_of_the_stated_tick_compares_what_it_held(tmp_path):
    root = tiny_nab.make_root(tmp_path, correct_ticks=80_000)
    result, record = tiny_nab.run(root, SEED + 4, 0.5)
    assert result["correct"], result["compared"]
    assert record["paused_s"] == 0.0
    assert result["compared_ticks"] == 8 * (record["n_chunks"] + 1)  # + warm-up


def test_followed_ticks_are_whole_chunks(tmp_path):
    root = tiny_nab.make_root(tmp_path, correct_ticks=12)
    with pytest.raises(ValueError, match="correct_ticks 12 is not a whole "
                                         "multiple of .* chunk_ticks 8"):
        tiny_nab.run(root, SEED, 0.5)


def test_committed_nab_configuration_states_its_followed_ticks():
    reg = Registry()
    cfg = reg.cell(tiny_nab.CELL)["config"]
    assert cfg["correct_ticks"] == 128
    assert cfg["correct_ticks"] % reg.cell(tiny_nab.CELL)["traffic"]["chunk_ticks"] == 0
    assert "first 128 ticks" in cfg["guarantees"]["scores"]
    assert "at tick 128" in cfg["guarantees"]["state"]
    # the cluster and node configurations follow every tick the window held
    # (by name: a later configuration may state the key)
    for name in ("cluster-256", "cluster-32", "node-3", "node-3-served"):
        assert "correct_ticks" not in reg._json("configs", name)
