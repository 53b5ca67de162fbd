"""The trace -> busy/idle/per-op/gap reduction, on a hand-made event list
(exact arithmetic) and on a reduced recording of a real TPU v5 lite trace."""

import json
import os

import pytest

from benchmark.trace_reduce import op_label, reduce, sync_offset_ns

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                       "fixtures", "trace_v5e_chunk_step.json")


def hand_made():
    # one device: a program of 400 ns holding a `while` (100..400) whose
    # body is two fusions, then idle, then a second program
    return {"/device:TPU:0": {
        "XLA Modules": [["jit_step(123)", 0, 400], ["jit_step(123)", 700, 200],
                        ["jit_other(9)", 950, 50]],
        "XLA Ops": [["%copy.1 = f32[8]{0} copy(%p)", 0, 100],
                    ["%while.4 = (s32[]) while(%t)", 100, 300],
                    ["%fusion.7 = pred[4,2]{1,0} fusion(%a)", 100, 200],
                    ["%fusion.8 = s32[16]{0} fusion(%b)", 300, 90],
                    ["%fusion.7 = pred[4,2]{1,0} fusion(%a)", 700, 200]]},
        "/host:CPU": {"annotations": [["bench_sync", 40, 10]]}}


def test_busy_idle_ops_modules_and_gaps_exact():
    r = reduce(hand_made(), (0, 1000),
               [("collect", 350, 650), ("sleep", 600, 720), ("tick", 0, 1000)])
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [0,400) and [700,900) -> 600 ns; the module at 950 has no op
    # events and so is not busy time
    assert r["busy_s"] == pytest.approx(600e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.7:pred[4,2]"] == pytest.approx(400e-9)
    assert ops["copy.1:f32[8]"] == pytest.approx(100e-9)
    assert ops["fusion.8:s32[16]"] == pytest.approx(90e-9)
    # the while's own time is what its body leaves: 300 - 200 - 90
    assert ops["while.4:s32[]"] == pytest.approx(10e-9)
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


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no /device:TPU"):
        reduce({"/host:CPU": {"annotations": []}}, (0, 10))
    with pytest.raises(ValueError, match="bench_sync"):
        sync_offset_ns({"/host:CPU": {"annotations": []}}, 1.0)


def test_op_label():
    assert op_label("%fusion.2 = pred[1024,256]{1,0:T(8,128)} fusion(%x)") \
        == "fusion.2:pred[1024,256]"
    assert op_label("%copy-start.44 = (pred[8]{0}, pred[8]{0}) copy-start(%y)") \
        == "copy-start.44:pred[8]"


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
    assert r["device_ops"][0][0].startswith("while.4") or \
        r["device_ops"][0][0].startswith("fusion.211")
    assert sum(s for _n, s in r["idle_gaps"]) == \
        pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
