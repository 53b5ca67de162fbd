"""The served path from inside: the readers of the program's host spans
(`rtap.loop.*`, `rtap.ingest.*`, `rtap.host.gc`, the `aot_warm` ring spans)
on hand-built event lists — exact arithmetic, the execution the tracer
clipped, counts that do not pair up — and every new metric file through the
Registry. No reader may raise on a trace that holds none of its spans: the
driver lays these files over a parent whose program writes none."""

import os
import sys
import types

import pytest

from benchmark import device_clock
from benchmark.registry import Registry
from tests.benchmark import manifest_rules as rules

HERE = os.path.dirname(__file__)
MODULE = "jit_chunk_step"
WINDOW = (1_000, 10_000_000)

#: the cells each family's lists began with (later cells follow them)
FIRST = {"live": ["cluster-256-live"],
         "replay": ["cluster-256-replay", "cluster-32-replay"]}

NEW = {  # metric -> (reader, cells), in the order they were added
    "ingest_snapshot_ms": ("group_phase", "live"),
    "loop_dispatch_ms": ("group_phase", "live"),
    "loop_emit_ms": ("group_phase", "live"),
    "tick_exposed_host_ms.live": ("exposed_host", "live"),
    "group_queue_ms.live": ("group_chain", "live"),
    "group_fetch_tail_ms.live": ("group_chain", "live"),
    "ingest_feed_ms": ("span_sum", "live"),
    "host_gc_ms.live": ("span_sum", "live"),
    "host_gc_ms.replay": ("span_sum", "replay"),
    "aot_warm_s": ("host_span_sum", "live"),
    "tm_learn_ms.live": ("scope_device", "live"),
    "tm_dendrite_ms.live": ("scope_device", "live"),
    "unscoped_ms.live": ("scope_device", "live"),
}


@pytest.fixture(scope="module")
def reg():
    return Registry()


def reader(reg, name):
    return reg._module("readers", name)


def note(name, start, dur, **args):
    return [name, start, dur, args]


def live_planes():
    """Two ticks of two groups on one chip. Tick 0: snapshot ends at 100,000
    ns; group a enqueued by 140,000 runs 150,000-250,000; group b enqueued by
    190,000 runs 250,000-350,000 (it queued 60,000 ns behind a); fetches end
    260,000 and 352,000; emit ends 400,000. The device is busy 200,000 of the
    300,000 ns between snapshot and emit. Tick 1 is tick 0 shifted by
    1,000,000 ns with a garbage collection of 5,000 ns inside its emit."""
    notes, modules, ops = [], [], []
    for k, t in ((0, 0), (1, 1_000_000)):
        notes += [
            note("rtap.loop.tick", t + 50_000, 370_000, tick=k),
            note("rtap.loop.source", t + 60_000, 45_000, tick=k),
            note("rtap.ingest.snapshot", t + 70_000, 30_000, tick=k,
                 wait_us=2),
            note("rtap.loop.dispatch", t + 110_000, 85_000, tick=k),
            note("rtap.group.enqueue", t + 120_000, 20_000, group="a", seq=k + 1),
            note("rtap.group.enqueue", t + 170_000, 20_000, group="b", seq=k + 1),
            note("rtap.loop.collect", t + 200_000, 155_000, tick=k),
            note("rtap.group.fetch", t + 205_000, 55_000, group="a", seq=k + 1),
            note("rtap.group.fetch", t + 300_000, 52_000, group="b", seq=k + 1),
            note("rtap.loop.emit", t + 360_000, 40_000, tick=k),
        ]
        modules += [[MODULE + "(123)", t + 150_000, 100_000],
                    [MODULE + "(123)", t + 250_000, 100_000]]
        ops += [["%fusion.1 = f32[8]{0} fusion(...)", t + 150_000, 100_000,
                 "jit(chunk_step)/rtap.tm.learn/add"],
                ["%fusion.2 = f32[8]{0} fusion(...)", t + 250_000, 100_000,
                 "jit(chunk_step)/rtap.tm.learn/add"]]
    notes.append(note("rtap.host.gc", 1_370_000, 5_000, generation=0,
                      collected=3))
    notes.append(note("rtap.ingest.feed", 500_000, 40_000, bytes=900,
                      wait_us=1))
    notes.append(note("rtap.ingest.feed", 560_000, 20_000, bytes=300,
                      wait_us=0))
    return {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops},
            "/host:CPU": {"annotations": notes}}


def test_exposed_host_is_the_interval_less_the_device(reg):
    eh = reader(reg, "exposed_host")
    ticks = eh.per_tick(live_planes(), WINDOW)
    assert sorted(ticks) == [0, 1]
    for k in (0, 1):
        assert ticks[k]["interval_ns"] == 300_000  # emit end - snapshot end
        assert ticks[k]["exposed_ns"] == 100_000   # ... - 200,000 busy
    # by the innermost span over it: 100,000-150,000 is 5,000 of source's
    # tail, 5,000 of the tick's own, 40,000 of dispatch (20,000 of it group
    # a's enqueue: the shorter span wins); 350,000-400,000 is 2,000 of group
    # b's fetch, 3,000 of collect, 5,000 of the tick's own, 40,000 of emit
    assert ticks[0]["by_span"] == {
        "rtap.loop.source": 5_000, "rtap.loop.tick": 10_000,
        "rtap.group.enqueue": 20_000, "rtap.loop.dispatch": 20_000,
        "rtap.group.fetch": 2_000, "rtap.loop.collect": 3_000,
        "rtap.loop.emit": 40_000}
    # tick 1's emit holds a 5,000 ns collection: the innermost span there
    assert ticks[1]["by_span"]["rtap.host.gc"] == 5_000
    assert ticks[1]["by_span"]["rtap.loop.emit"] == 35_000
    assert sum(ticks[1]["by_span"].values()) == 100_000


def test_exposed_host_counts_only_ticks_inside_the_window(reg):
    eh = reader(reg, "exposed_host")
    ticks = eh.per_tick(live_planes(), (1_000_000, 10_000_000))
    assert sorted(ticks) == [1]
    assert eh.per_tick(live_planes(), (5_000_000, 10_000_000)) is None


def test_exposed_host_places_the_device_on_the_hosts_clock(reg):
    """The device's line is 2,000 ns early on the host's clock (b's fetch
    ends 2,000 ns after its program does, the round's least): placed, the
    lead-in before a's program is 2,000 ns longer — dispatch's — and b's
    fetch has no exposed tail left; the total does not move."""
    eh = reader(reg, "exposed_host")
    planes = live_planes()
    triples = device_clock.chain(planes, MODULE, WINDOW)
    ticks = eh.per_tick(planes, WINDOW, triples)
    assert ticks[0]["exposed_ns"] == 100_000
    assert ticks[0]["by_span"] == {
        "rtap.loop.source": 5_000, "rtap.loop.tick": 10_000,
        "rtap.group.enqueue": 20_000, "rtap.loop.dispatch": 22_000,
        "rtap.loop.collect": 3_000, "rtap.loop.emit": 40_000}


def test_exposed_host_through_its_metric_file(reg, capsys):
    definition, module = reg.layer_metric("tick_exposed_host_ms.live")
    record = {"trace": {"window_s": 0.01}, "scoped_planes": with_sync(
        live_planes())}
    assert module.read(record, definition) == pytest.approx(0.1)
    said = capsys.readouterr().out
    assert "2 traced ticks 0..1" in said
    assert "0.300 ms a tick = device busy 0.200 + exposed host 0.100;" in said
    assert "rtap.loop.dispatch 0.022" in said  # placed by the chain
    assert module.read({"trace": None}, definition) is None  # untraced
    # a trace whose chunks do not pair up: the device's line as recorded
    planes = with_sync(live_planes())
    del planes["/device:TPU:0"]["XLA Modules"][2]
    record = {"trace": {"window_s": 0.01}, "scoped_planes": planes}
    assert module.read(record, definition) == pytest.approx(0.1)
    said = capsys.readouterr().out
    assert "nothing paired" in said and "as recorded" in said
    assert "rtap.loop.dispatch 0.020" in said


def with_sync(planes, at=1_000):
    planes["/host:CPU"]["annotations"].append(["bench_sync", at, 1_000, {}])
    return planes


def test_group_chain_exact_arithmetic(reg):
    triples = device_clock.chain(live_planes(), MODULE, WINDOW)
    assert [(t["group"], t["seq"]) for t in triples] == [
        ("a", 1), ("b", 1), ("a", 2), ("b", 2)]
    # each round's bracket: a's program starts 30,000 ns after its enqueue
    # began, b's fetch ends 2,000 ns after its program: [-30,000, 2,000];
    # the upper end places the device's line
    assert {(t["offset_low"], t["offset_high"], t["offset"])
            for t in triples} == {(-30_000, 2_000, 2_000)}
    assert [t["device_start"] - t["enqueue_end"] for t in triples] == [
        12_000, 62_000, 12_000, 62_000]
    # both fetches began before their scores existed; b's is the round's
    # quickest (0 by construction), a's ended 8,000 ns later than that
    assert [t["fetch_end"] - max(t["device_end"], t["fetch_start"])
            for t in triples] == [8_000, 0, 8_000, 0]
    record = {"trace": {"window_s": 0.01},
              "scoped_planes": with_sync(live_planes())}
    definition, module = reg.layer_metric("group_queue_ms.live")
    assert module.read(record, definition) == pytest.approx(0.037)
    definition, module = reg.layer_metric("group_fetch_tail_ms.live")
    assert module.read(record, definition) == pytest.approx(0.004)


def test_group_chain_drops_the_execution_the_tracer_clipped(reg):
    planes = live_planes()
    # a program was running when the profiler started: it holds the
    # device's first recorded op, and its enqueue precedes the window
    planes["/device:TPU:0"]["XLA Modules"].insert(
        0, [MODULE + "(123)", 20_000, 60_000])
    planes["/device:TPU:0"]["XLA Ops"].insert(
        0, ["%fusion.9 = f32[8]{0} fusion(...)", 20_000, 60_000, ""])
    triples = device_clock.chain(planes, MODULE, WINDOW)
    assert len(triples) == 4
    assert triples[0]["raw_start"] == 150_000


def test_the_offset_between_the_two_clocks_is_bracketed_round_by_round(
        reg, capsys):
    planes = live_planes()
    # tick 1's device events placed 35,000 ns early on the host's timeline:
    # group a's program would start 5,000 ns before its enqueue does
    for line in planes["/device:TPU:0"].values():
        for event in line[2:]:
            event[1] -= 35_000
    triples = device_clock.chain(planes, MODULE, WINDOW)
    assert [(t["offset_low"], t["offset_high"]) for t in triples] == [
        (-30_000, 2_000)] * 2 + [(5_000, 37_000)] * 2
    # placed, the two rounds read alike
    assert [t["device_start"] - t["enqueue_end"] for t in triples] == [
        12_000, 62_000, 12_000, 62_000]
    definition, module = reg.layer_metric("group_queue_ms.live")
    record = {"trace": {"window_s": 0.01}, "scoped_planes": with_sync(planes)}
    assert module.read(record, definition) == pytest.approx(0.037)
    said = capsys.readouterr().out
    assert "1: [-30.0, 2.0]; 2: [5.0, 37.0]" in said
    assert "first program starts 0.032 ms after its enqueue began" in said
    # and the exposed host time of tick 1 is what tick 0's is
    eh = reader(reg, "exposed_host")
    ticks = eh.per_tick(planes, WINDOW, triples)
    assert ticks[1]["exposed_ns"] == ticks[0]["exposed_ns"] == 100_000
    assert eh.per_tick(planes, WINDOW)[1]["by_span"]["rtap.group.fetch"] \
        == 37_000  # as recorded: 35,000 ns of lead-in booked under a fetch


def test_group_chain_says_why_it_reads_nothing(reg, capsys):
    planes = live_planes()
    del planes["/device:TPU:0"]["XLA Modules"][2]
    with pytest.raises(device_clock.NoChain, match="4 enqueue.*3 whole"):
        device_clock.chain(planes, MODULE, WINDOW)
    definition, module = reg.layer_metric("group_queue_ms.live")
    record = {"trace": {"window_s": 0.01}, "scoped_planes": with_sync(planes)}
    assert module.read(record, definition) is None
    assert "nothing paired" in capsys.readouterr().out
    # an execution that starts 10,000 ns before its own enqueue does while
    # another ends 2,000 ns before its fetch does: no offset between the
    # device's clock and the host's allows both
    planes = live_planes()
    planes["/device:TPU:0"]["XLA Modules"][0][1] = 110_000
    with pytest.raises(device_clock.NoChain,
                       match="seq 1: .*10.0 us before its enqueue"):
        device_clock.chain(planes, MODULE, WINDOW)
    # a chunk whose fetch the window does not hold
    planes = live_planes()
    planes["/host:CPU"]["annotations"] = [
        n for n in planes["/host:CPU"]["annotations"]
        if not (n[0] == "rtap.group.fetch" and n[3] == {"group": "b",
                                                          "seq": 2})]
    with pytest.raises(device_clock.NoChain, match="no fetch"):
        device_clock.chain(planes, MODULE, WINDOW)
    # no enqueue annotation at all: nothing to say
    assert device_clock.chain(
        {"/device:TPU:0": {}, "/host:CPU": {"annotations": []}},
        MODULE, WINDOW) is None


def test_span_sum_per_tick_and_per_second(reg):
    ss = reader(reg, "span_sum")
    planes = live_planes()
    # 60,000 ns of parsing over the window's two ticks
    assert ss.total_ms(planes, "rtap.ingest.feed", "tick", WINDOW) == \
        pytest.approx(0.03)
    # 5,000 ns of collection in a window of 9,999,000 ns
    assert ss.total_ms(planes, "rtap.host.gc", "second", WINDOW) == \
        pytest.approx(0.005 / 0.009999)
    # every thread's events count; only those wholly inside the window
    assert ss.total_ms(planes, "rtap.host.gc", "second",
                       (1_000, 1_372_000)) is None
    with pytest.raises(ValueError, match="unknown 'per'"):
        ss.total_ms(planes, "rtap.host.gc", "chunk", WINDOW)


def test_span_sum_zero_only_where_the_program_declares_the_name(reg):
    ss = reader(reg, "span_sum")
    quiet = {"/host:CPU": {"annotations": [
        note("rtap.loop.tick", 2_000, 1_000, tick=0)]}}
    # a commit before the seam: the metric is left out
    assert ss.total_ms(quiet, "rtap.host.gc", "second", WINDOW) is None
    # the program declares the span and no collection ran: 0
    assert ss.total_ms(quiet, "rtap.host.gc", "second", WINDOW,
                       vocabulary=("rtap.host.gc",)) == 0.0
    # per tick with no tick in the window: nothing to divide by
    assert ss.total_ms({"/host:CPU": {"annotations": []}}, "rtap.ingest.feed",
                       "tick", WINDOW, vocabulary=("rtap.ingest.feed",)) \
        is None


def test_span_sum_through_its_metric_files(reg, capsys):
    record = {"trace": {"window_s": 0.01},
              "scoped_planes": with_sync(live_planes())}
    definition, module = reg.layer_metric("ingest_feed_ms")
    assert module.read(record, definition) == pytest.approx(0.03)
    for name in ("host_gc_ms.live", "host_gc_ms.replay"):
        definition, module = reg.layer_metric(name)
        assert module.read(record, definition) == pytest.approx(0.5)
        assert "rtap.host.gc in the traced window, generation: count, ms: " \
            "0: 1, 0.005" in capsys.readouterr().out
        assert module.read({"trace": None}, definition) is None


def test_host_span_sum(reg):
    definition, module = reg.layer_metric("aot_warm_s")
    spans = [("aot_warm", 10.0, 1.25), ("source", 12.0, 0.5),
             ("aot_warm", 11.5, 0.25), ("cadence_sleep", 13.0, 4.0)]
    assert module.read({"host_spans": spans}, definition) == 1.5
    # the parent's loop records no such span; a replay record has no list
    assert module.read({"host_spans": spans[1:2]}, definition) is None
    assert module.read({}, definition) is None


def test_loop_phases_read_through_the_group_phase_reader(reg):
    record = {"trace": {"window_s": 0.01},
              "scoped_planes": with_sync(live_planes())}
    want = {"ingest_snapshot_ms": 0.03, "loop_dispatch_ms": 0.085,
            "loop_emit_ms": 0.04}
    for name, ms in want.items():
        definition, module = reg.layer_metric(name)
        assert module.read(record, definition) == pytest.approx(ms)


def test_live_scope_metrics_read_the_live_program(reg):
    planes = live_planes()
    for line in planes["/device:TPU:0"].values():
        for event in line[1::2]:  # a real device leaves a gap between two
            event[1] += 1_000     # programs: each tick's second starts
            event[2] -= 1_000     # 1,000 ns late
    record = {"trace": {"window_s": 0.01}, "chunk_ticks": 1,
              "scoped_planes": with_sync(planes)}
    definition, module = reg.layer_metric("tm_learn_ms.live")
    # four executions of one tick (scoped_trace.by_scope leaves out the
    # one that holds the device's first op): (99,000 + 100,000 + 99,000) / 3
    assert module.read(record, definition) == pytest.approx(0.298 / 3)
    definition, module = reg.layer_metric("unscoped_ms.live")
    assert module.read(record, definition) == 0.0
    assert definition["module"] == reg.layer_metric(
        "step_device_ms.live")[0]["module"]


def test_a_trace_of_the_parent_reads_none_of_the_new_spans(reg, monkeypatch):
    """The parent's program writes `rtap.group.*` and the device's scopes
    only, and its obs/trace.py declares no vocabulary; over its trace every
    reader of a new span returns None and none raises. The three `.live`
    scope metrics and the chunk chain read what it already emits."""
    monkeypatch.setitem(sys.modules, "rtap_tpu.obs.trace",
                        types.SimpleNamespace())
    planes = live_planes()
    planes["/host:CPU"]["annotations"] = [
        n for n in planes["/host:CPU"]["annotations"]
        if n[0].startswith("rtap.group.")]
    record = {"trace": {"window_s": 0.01}, "chunk_ticks": 1,
              "host_spans": [("source", 1.0, 0.1), ("cadence_sleep", 2.0, 4.0)],
              "scoped_planes": with_sync(planes)}
    read = {}
    for name in NEW:
        definition, module = reg.layer_metric(name)
        read[name] = module.read(record, definition)
    assert {k for k, v in read.items() if v is not None} == {
        "group_queue_ms.live", "group_fetch_tail_ms.live",
        "tm_learn_ms.live", "tm_dendrite_ms.live", "unscoped_ms.live"}


def metric_holds(reg: Registry, name: str) -> None:
    want_reader, cells = NEW[name]
    entry = rules.entry(reg.manifest["per_layer"], name)
    definition = rules.agrees_with_definition(reg, entry)
    assert definition["name"] == name and definition["reader"] == want_reader
    # the cells the list was accepted with come first, in their order; a
    # later cell is appended after them (tests/benchmark/manifest_rules.py)
    rules.starts_with(entry["workloads"], FIRST[cells])
    for cell in entry["workloads"]:
        assert name in [m["name"] for m in reg.metrics(cell, "per_layer")]


def manifest_holds(reg: Registry) -> None:
    """What this file holds of a manifest: the committed one, and the
    rehearsal's copy (tests/benchmark/room.py)."""
    for name in NEW:
        metric_holds(reg, name)
    rules.added_in_order(reg.manifest["per_layer"], NEW)


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_resolves_and_is_listed_for_its_cells(reg, name):
    metric_holds(reg, name)


def test_the_new_metrics_stand_in_the_order_they_were_added(reg):
    manifest_holds(reg)
