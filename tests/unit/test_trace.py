"""obs/trace.py: the span-ring recorder the serve loop flies with blind
(ISSUE 4 tentpole). Pins the parts everything downstream depends on:
strictly bounded memory (ring size x record size — the flight recorder's
"black box can run forever" contract), overwrite-oldest semantics,
Chrome trace-event JSON schema (Perfetto loads exactly this), the tick
window filter the /trace route and bundle dumps use, lock-free
multi-thread capture, and the <= 1% tick-budget overhead bar."""

import json
import threading
import time

import pytest

from rtap_tpu.obs.trace import REC_DTYPE, TraceRecorder


@pytest.mark.quick
def test_ring_is_strictly_bounded_and_overwrites_oldest():
    tr = TraceRecorder(capacity=8)
    t0 = time.perf_counter()
    for i in range(20):
        tr.add_span("tick", i, t0 + i * 1e-3, 1e-4)
    assert tr.total == 20
    assert tr.dropped == 12
    recs = tr.records()
    assert len(recs) == 8
    # oldest overwritten: only the last capacity ticks remain
    assert sorted(r["tick"] for r in recs) == list(range(12, 20))
    # the memory bound the flight-recorder contract rests on: ONE
    # preallocated structured array per writer thread, never grown
    assert tr.nbytes() == 8 * REC_DTYPE.itemsize


@pytest.mark.quick
def test_instant_payloads_are_truncated_and_memory_stays_flat():
    tr = TraceRecorder(capacity=4, max_arg_bytes=32)
    for i in range(10):
        tr.add_instant("group_quarantined", i, {"blob": "x" * 10_000})
    shard = next(iter(tr._shards.values()))
    assert len(shard.aux) == 4
    assert all(a is None or len(a) <= 32 for a in shard.aux)


@pytest.mark.quick
def test_name_interning_is_bounded():
    tr = TraceRecorder(capacity=64, max_names=4)
    t0 = time.perf_counter()
    for i in range(10):
        tr.add_span(f"name{i}", 0, t0, 1e-6)
    # vocabulary overflow maps to "<other>" instead of growing the table
    assert len(tr._names_rev) == 4
    names = {r["name"] for r in tr.records()}
    assert "<other>" in names


@pytest.mark.quick
def test_chrome_trace_schema_spans_instants_and_group_tracks():
    tr = TraceRecorder(capacity=64)
    t0 = time.perf_counter()
    tr.add_span("source", 3, t0, 0.002)
    tr.add_span("dispatch", 3, t0 + 0.002, 0.004, group=1)
    tr.add_instant("group_quarantined", 3, {"phase": "dispatch"}, group=1)
    ct = json.loads(json.dumps(tr.chrome_trace()))  # must round-trip
    evs = ct["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    instants = [e for e in evs if e.get("ph") == "i"]
    assert len(spans) == 2 and len(instants) == 1
    src = next(e for e in spans if e["name"] == "source")
    assert src["tid"] == 0 and src["args"]["tick"] == 3
    assert src["dur"] == pytest.approx(2000, rel=0.01)  # microseconds
    disp = next(e for e in spans if e["name"] == "dispatch")
    assert disp["tid"] == 2 and disp["args"]["group"] == 1  # group g -> tid g+1
    q = instants[0]
    assert q["name"] == "group_quarantined" and q["s"] == "g"
    assert q["args"]["tick"] == 3 and q["args"]["phase"] == "dispatch"
    # track naming metadata present for the loop and the group
    meta = {(e["tid"], e["args"]["name"]) for e in evs if e.get("ph") == "M"}
    assert (0, "serve loop") in meta and (2, "group1") in meta


@pytest.mark.quick
def test_profiler_sync_reading_is_in_other_data_and_on_the_timeline():
    # serve --jax-trace: one instant at the reading the device trace's
    # `rtap.sync` annotation stands for, kept whatever the tick window
    tr = TraceRecorder(capacity=64)
    assert tr.chrome_trace()["otherData"]["profiler_sync_perf"] is None
    t = time.perf_counter()
    tr.profiler_sync(t)
    tr.add_span("tick", 9, t + 1.0, 0.5)
    ct = json.loads(json.dumps(tr.chrome_trace(last_ticks=1)))
    assert ct["otherData"]["profiler_sync_perf"] == t
    (mark,) = [e for e in ct["traceEvents"] if e["name"] == "profiler_sync"]
    assert mark["ph"] == "i" and mark["args"]["perf_counter"] == t
    assert mark["ts"] == pytest.approx(
        (t - ct["otherData"]["epoch_perf"]) * 1e6, abs=0.01)


@pytest.mark.quick
def test_last_ticks_window_filters_by_tick_not_position():
    tr = TraceRecorder(capacity=64)
    t0 = time.perf_counter()
    for i in range(10):
        tr.add_span("tick", i, t0 + i, 0.5)
    recs = tr.records(last_ticks=3)
    assert sorted(r["tick"] for r in recs) == [7, 8, 9]
    ct = tr.chrome_trace(last_ticks=3)
    assert all(e["args"]["tick"] >= 7 for e in ct["traceEvents"]
               if e.get("ph") == "X")


@pytest.mark.quick
def test_concurrent_writers_have_private_shards():
    tr = TraceRecorder(capacity=1000)
    t0 = time.perf_counter()
    # all 4 workers alive simultaneously: thread idents are only unique
    # among LIVE threads (CPython reuses them), and the shard-per-thread
    # claim is about concurrent writers
    barrier = threading.Barrier(4)

    def work():
        barrier.wait()
        for i in range(500):
            tr.add_span("collect", i, t0, 1e-6, group=0)
        barrier.wait()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.add_span("tick", 0, t0, 1e-6)
    # every append landed (each thread owns its ring; nothing raced away)
    assert tr.total == 4 * 500 + 1
    assert tr.dropped == 0
    assert len(tr._shards) >= 4


@pytest.mark.quick
def test_span_args_ride_into_the_chrome_export():
    # a span may carry a serialized JSON object (the seam's garbage-
    # collection spans do): bounded like an instant's payload
    tr = TraceRecorder(capacity=8, max_arg_bytes=32)
    t = time.perf_counter()
    tr.add_span("gc", -1, t, 0.001, args_json='{"generation": 2}')
    tr.add_span("gc", -1, t + 1, 0.001, args_json='{"x": "' + "y" * 64 + '"}')
    tr.add_span("tick", 0, t + 2, 0.5)
    a, b, c = tr.records()
    assert a["args_json"] == '{"generation": 2}' and len(b["args_json"]) == 32
    assert "args_json" not in c
    ev = [e for e in tr.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    assert ev[0]["args"] == {"tick": -1, "generation": 2}
    assert ev[1]["args"]["tick"] == -1 and "info" in ev[1]["args"]  # cut


@pytest.mark.quick
def test_trace_and_flight_overhead_within_one_percent_of_tick_budget():
    """ISSUE 4 acceptance: span-ring + flight-recorder traffic for a full
    16-group tick costs <= 1% of the 1 s cadence (the same bar, and the
    same measurement, as python -m rtap_tpu.obs.selfbench's second line)."""
    from rtap_tpu.obs.selfbench import measure_trace

    res = measure_trace(n=5000)
    assert res["per_tick_overhead_frac"] <= 0.01, res
