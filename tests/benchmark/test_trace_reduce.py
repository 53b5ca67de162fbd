"""The trace -> busy/idle/per-op/gap reduction, on a hand-made event list
(exact arithmetic) and on reduced recordings of real TPU v5 lite traces."""

import json
import os
import re

import pytest

from benchmark.trace_reduce import (
    _self_times, op_label, reduce, sync_offset_ns)

FIXTURES = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                        "fixtures")
FIXTURE = os.path.join(FIXTURES, "trace_v5e_chunk_step.json")
SCOPED = os.path.join(FIXTURES, "trace_v5e_scoped.json")
LEARN = "jit(chunk_step)/while/body/closed_call/vmap(jit(tm_step))/rtap.tm.learn/"


def hand_made():
    # one device: a program of 400 ns holding a `while` (100..400) whose
    # body is two fusions, then idle, then a second program whose fusion XLA
    # numbered otherwise; op events with and without an op_name
    return {"/device:TPU:0": {
        "XLA Modules": [["jit_step(123)", 0, 400], ["jit_step(123)", 700, 200],
                        ["jit_other(9)", 950, 50]],
        "XLA Ops": [["%copy.1 = f32[8]{0} copy(%p)", 0, 100],
                    ["%while.4 = (s32[]) while(%t)", 100, 300, "jit(step)/while"],
                    ["%fusion.7 = pred[4,2]{1,0} fusion(%a)", 100, 200,
                     LEARN + "select_n"],
                    ["%fusion.8 = s32[16]{0} fusion(%b)", 300, 90,
                     LEARN + "rtap.tm.learn.rows/scatter"],
                    ["%fusion.31 = pred[4,2]{1,0} fusion(%a)", 700, 200,
                     LEARN + "select_n"]]},
        "/host:CPU": {"annotations": [["bench_sync", 40, 10, {}]]}}


def test_busy_idle_ops_modules_and_gaps_exact():
    r = reduce(hand_made(), (0, 1000),
               [("collect", 350, 650), ("sleep", 600, 720), ("tick", 0, 1000)])
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [0,400) and [700,900) -> 600 ns; the module at 950 has no op
    # events and so is not busy time
    assert r["busy_s"] == pytest.approx(600e-9)
    ops = dict(r["device_ops"])
    # equal work under one scope adds up, whatever XLA numbered it
    assert ops["rtap.tm.learn/fusion:pred[4,2]"] == pytest.approx(400e-9)
    assert ops["-/copy:f32[8]"] == pytest.approx(100e-9)
    assert ops["rtap.tm.learn.rows/fusion:s32[16]"] == pytest.approx(90e-9)
    # the while's own time is what its body leaves: 300 - 200 - 90
    assert ops["-/while:s32[]"] == pytest.approx(10e-9)
    assert len(ops) == 4
    assert r["modules"]["jit_step"] == {"count": 2,
                                        "seconds": pytest.approx(600e-9)}
    assert r["modules"]["jit_other"]["count"] == 1
    # gaps [400,700) and [900,1000): the shortest covering span wins
    gaps = dict(r["idle_gaps"])
    assert gaps["sleep"] == pytest.approx(100e-9)  # [600,700)
    assert gaps["collect"] == pytest.approx(200e-9)  # [400,600)
    assert gaps["tick"] == pytest.approx(100e-9)  # [900,1000)
    assert "unattributed" not in gaps


def test_window_clips_and_uncovered_gap_is_unattributed():
    r = reduce(hand_made(), (200, 800), [])
    assert r["busy_s"] == pytest.approx((200 + 100) * 1e-9)
    assert dict(r["idle_gaps"]) == {"unattributed": pytest.approx(300e-9)}
    # only whole executions inside the window are counted as programs
    assert r["modules"] == {}


def _gaps_by_every_span(busy, window, spans):
    """The attribution as first written: every gap held against every span,
    shortest first (ties: the caller's order)."""
    gaps, cursor = [], window[0]
    for a, b in sorted(busy):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    out = {}
    for gap in gaps:
        left = [gap]
        for name, s, e in sorted((sp for sp in spans if sp[2] > sp[1]),
                                 key=lambda sp: sp[2] - sp[1]):
            cut = []
            for a, b in left:
                lo, hi = max(a, s), min(b, e)
                if hi > lo:
                    out[name] = out.get(name, 0) + (hi - lo)
                    cut += [(a, lo)] * (a < lo) + [(hi, b)] * (hi < b)
                else:
                    cut.append((a, b))
            left = cut
        if left:
            out["unattributed"] = out.get("unattributed", 0) + \
                sum(b - a for a, b in left)
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_gap_sweep_attributes_as_every_gap_against_every_span(seed):
    # a long window of many small gaps and many spans — nested, tied in
    # length, empty, before and past the window: the sweep keeps only the
    # spans that can overlap a gap and must give what the full product gives
    import random
    rnd = random.Random(seed)
    busy, t = [], 0
    for _ in range(300):
        t += rnd.randint(1, 40)
        d = rnd.randint(5, 60)
        busy.append((t, t + d))
        t += d
    window = (busy[3][0] + 1, t - 7)
    names = ["dispatch", "collect_wait", "collect_host", "feed", "tick"]
    spans = [("tick", -50, t // 3), ("tick", t // 2, t + 90)]
    for _ in range(400):
        s = rnd.randint(-200, t + 100)
        spans.append((rnd.choice(names), s,
                      s + rnd.choice([0, 3, 3, 17, 17, 120, 250])))
    planes = {"/device:TPU:0": {
        "XLA Ops": [["%fusion.1 = f32[8]{0} fusion(%a)", a, b - a]
                    for a, b in busy]}}
    r = reduce(planes, window, spans, top=99)
    clipped = [(max(a, window[0]), min(b, window[1])) for a, b in busy
               if b > window[0] and a < window[1]]
    want = _gaps_by_every_span(clipped, window, spans)
    assert dict(r["idle_gaps"]) == {
        k: pytest.approx(v * 1e-9, rel=1e-12) for k, v in want.items()}
    assert len(want) > 3 and "unattributed" in want


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no /device:TPU"):
        reduce({"/host:CPU": {"annotations": []}}, (0, 10))
    with pytest.raises(ValueError, match="bench_sync"):
        sync_offset_ns({"/host:CPU": {"annotations": []}}, 1.0)


@pytest.mark.parametrize("hlo,op_name,label", [
    ("%fusion.194 = s32[1024,256,192]{2,1,0:T(8,128)} fusion(%x)",
     LEARN + "select_n", "rtap.tm.learn/fusion:s32[1024,256,192]"),
    ("%fusion.2 = pred[1024,256]{1,0:T(8,128)} fusion(%x)", "",
     "-/fusion:pred[1024,256]"),
    ("%copy-start.44 = (pred[8]{0}, pred[8]{0}) copy-start(%y)",
     "jit(chunk_step)/while/body/copy", "-/copy-start:pred[8]"),
    # the innermost scope owns the op; a name without a number keeps itself
    ("%reduce-window = f32[8]{0} reduce-window(%y)",
     LEARN + "rtap.tm.learn.rows/vmap(rtap.encode)/cumsum",
     "rtap.encode/reduce-window:f32[8]"),
    ("not hlo at all", LEARN + "x", "rtap.tm.learn/not hlo at all"),
])
def test_op_label(hlo, op_name, label):
    assert op_label(hlo, op_name) == label


def test_op_label_fits_a_ledger_line():
    long = op_label("%fusion.9 = s32[" + ",".join(["1024"] * 20) + "]{0} fusion(%x)",
                    LEARN + "select_n")
    assert len(long) == 64 and long.startswith("rtap.tm.learn/fusion:s32[1024,")


def test_recorded_v5e_trace():
    with open(FIXTURE) as f:
        rec = json.load(f)
    planes = rec["planes"]
    off = sync_offset_ns(planes, rec["sync_perf_s"])
    sync_ns = next(e[1] for e in planes["/host:CPU"]["annotations"]
                   if e[0] == "bench_sync")
    assert off == sync_ns - int(rec["sync_perf_s"] * 1e9)
    mods = planes["/device:TPU:0"]["XLA Modules"]
    w0, w1 = sync_ns, mods[-1][1] + mods[-1][2] + 1_000_000
    spans = [(n, s, s + d) for n, s, d in planes["/host:CPU"]["annotations"]
             if n != "bench_sync"]
    r = reduce(planes, (w0, w1), spans)
    assert r["modules"]["jit_chunk_step"]["count"] == 2
    # two 8-tick programs of G=1024 at ~201 ms a tick (PERF.md, PR 21/24)
    assert r["modules"]["jit_chunk_step"]["seconds"] / 16 == \
        pytest.approx(0.2011, abs=0.0005)
    # the device is busy for all but the host's turn-around between programs
    assert 0.98 < r["busy_s"] / r["window_s"] < 1.0
    # a recording from before the scopes: every label is unscoped
    assert all(label.startswith("-/") for label, _s in r["device_ops"])
    assert r["device_ops"][0][0].split(":")[0] in ("-/while", "-/fusion")
    assert sum(s for _n, s in r["idle_gaps"]) == \
        pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_recorded_scoped_trace_labels_name_the_scope():
    """The breakdown of a recording that carries the program's scopes
    (cluster-256-replay, PR 25): every label is `<scope or ->/<kind>:<type>`
    with no XLA number, equal work adds up, and no time is lost by it."""
    with open(SCOPED) as f:
        planes = json.load(f)["cluster-256-replay"]["planes"]
    ops = planes["/device:TPU:0"]["XLA Ops"]
    w = (min(e[1] for e in ops), max(e[1] + e[2] for e in ops))
    r = reduce(planes, w, top=10_000)
    labels = dict(r["device_ops"])
    pattern = re.compile(r"^(-|rtap\.[a-z_.]+)/[A-Za-z_-]+(:[a-z0-9]+\[[0-9,]*\]?)?")
    for label in labels:
        assert pattern.match(label) and len(label) <= 64, label
        assert not re.search(r"/[^:]*\.\d+(:|$)", label), label
    assert any(label.startswith("rtap.tm.learn/") for label in labels)
    assert any(label.startswith("-/") for label in labels)
    # grouping only adds: fewer labels than distinct ops, the same seconds
    assert len(labels) < len({e[0] for e in ops})
    assert sum(labels.values()) == pytest.approx(
        sum(ns for _n, ns in _self_times([e[:3] for e in ops])) / 1e9)
    top = reduce(planes, w)["device_ops"]
    assert len(top) == 10 and top == r["device_ops"][:10]
