"""The program's own names in a trace -> per-scope device time, per-kernel
roofline shares and per-phase host time: exact arithmetic on hand-made event
lists, the .xplane.pb reader on a hand-encoded file, and all of it on a
reduced recording of this PR's own TPU v5 lite traces."""

import json
import os
import struct

import pytest

from benchmark import scoped_trace as st
from benchmark.kernel_bytes import (KERNELS, STATE_LEAVES, kernel_bytes_per_stream,
                                    kernel_floor_seconds, leaf_bytes)
from benchmark.registry import Registry
from benchmark.roofline import state_bytes_per_stream
from tests.benchmark import manifest_rules as rules

HERE = os.path.dirname(__file__)
FIXTURE = os.path.join(HERE, "..", "..", "benchmark", "fixtures",
                       "trace_v5e_scoped.json")
CONFIGS = os.path.join(HERE, "..", "..", "benchmark", "configs")

SCOPE_MS = {"encode_ms.replay": "rtap.encode",
            "sp_overlap_ms.replay": "rtap.sp.overlap",
            "sp_inhibit_ms.replay": "rtap.sp.inhibit",
            "sp_learn_ms.replay": "rtap.sp.learn",
            "tm_activate_ms.replay": "rtap.tm.activate",
            "tm_learn_ms.replay": "rtap.tm.learn",
            "tm_dendrite_ms.replay": "rtap.tm.dendrite",
            "unscoped_ms.replay": "unscoped"}
ROOFLINES = {"sp_overlap_roofline.replay": "rtap.sp.overlap",
             "sp_learn_roofline.replay": "rtap.sp.learn",
             "tm_roofline.replay": "rtap.tm"}
PHASES = {"group_stage_ms.replay": ("rtap.group.stage", "chunk"),
          "group_enqueue_ms.replay": ("rtap.group.enqueue", "chunk"),
          "group_fetch_ms.replay": ("rtap.group.fetch", "chunk"),
          "group_likelihood_ms.replay": ("rtap.group.likelihood", "chunk"),
          "group_fetch_ms.live": ("rtap.group.fetch", "tick"),
          "group_likelihood_ms.live": ("rtap.group.likelihood", "tick")}


def model(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return json.load(f)


SP = "jit(step)/while/body/closed_call/vmap(jit(sp_step))/rtap.sp.overlap/gather:"
ENC = "jit(step)/while/body/closed_call/vmap(rtap.encode)/and:"


def hand_made():
    # a program clipped by the tracer's start (it holds the device's first
    # op), then two whole programs of 2 ticks each whose SP fusion XLA
    # numbered differently, then a program cut by the window's end
    return {"/device:TPU:0": {
        "XLA Modules": [["jit_step(1)", 100, 150], ["jit_step(1)", 300, 400],
                        ["jit_step(2)", 800, 400], ["jit_step(2)", 1300, 400],
                        ["jit_other(3)", 1250, 20]],
        "XLA Ops": [["%fusion.7 = pred[8]{0} fusion(%a)", 100, 150, SP],
                    ["%copy.1 = s16[8]{0} copy(%p)", 300, 40, "jit(step)/while:"],
                    ["%while.4 = (s32[]) while(%t)", 340, 360, ""],
                    ["%fusion.7 = pred[8]{0} fusion(%a)", 340, 300, SP],
                    ["%fusion.8 = s32[4]{0} fusion(%b)", 640, 50, ENC],
                    ["%copy.1 = s16[8]{0} copy(%p)", 800, 40, "jit(step)/while:"],
                    ["%while.4 = (s32[]) while(%t)", 840, 360, ""],
                    ["%fusion.9 = pred[8]{0} fusion(%a)", 840, 340, SP],
                    ["%fusion.8 = s32[4]{0} fusion(%b)", 1180, 10, ENC],
                    ["%fusion.9 = pred[8]{0} fusion(%a)", 1300, 400, SP]]},
        "/host:CPU": {"annotations": [
            ["bench_sync", 50, 5, {}],
            ["rtap.group.stage", 60, 10, {"group": "a", "seq": 2}],
            ["rtap.group.stage", 80, 30, {"group": "b", "seq": 2}],
            ["rtap.group.stage", 700, 20, {"group": "a", "seq": 3}],
            ["rtap.group.stage", 730, 40, {"group": "b", "seq": 3}],
            ["rtap.group.fetch", 1190, 600, {"group": "a", "seq": 2}]]}}


def test_by_scope_exact():
    planes = hand_made()
    window = st.traced_window(planes, 1600e-9)
    assert window == (50, 1650)
    table = st.by_scope(planes, "jit_step", 2, window)
    # two whole executions x 2 ticks; the clipped one at 100 and the one
    # the window cuts at 1300 are left out
    per = 2 * 2 * 1e6
    assert table == {
        "rtap.sp.overlap": pytest.approx((300 + 340) / per),
        "rtap.encode": pytest.approx((50 + 10) / per),
        # the copies, and what the while's body leaves of it: 10 + 10
        "unscoped": pytest.approx((40 + 10 + 40 + 10) / per)}
    assert sum(table.values()) == pytest.approx(800 / per)
    # without a window the last program is whole too
    assert st.by_scope(planes, "jit_step", 2)["rtap.sp.overlap"] == \
        pytest.approx((300 + 340 + 400) / (3 * 2 * 1e6))
    assert st.by_scope(planes, "jit_nope", 2, window) is None
    assert st.by_scope(planes, "jit_step", 2, (0, 200)) is None


def test_a_program_without_scopes_is_an_error():
    planes = hand_made()
    for ev in planes["/device:TPU:0"]["XLA Ops"]:
        ev[3] = "jit(step)/while/body/gather:"
    with pytest.raises(st.NoScopes, match="not one rtap. scope"):
        st.by_scope(planes, "jit_step", 2)
    with pytest.raises(ValueError, match="bench_sync"):
        st.traced_window({"/host:CPU": {"annotations": []}}, 1.0)


@pytest.mark.parametrize("op_name,scope", [
    (SP, "rtap.sp.overlap"), (ENC, "rtap.encode"),
    ("jit(f)/rtap.tm.learn/cond/branch_1_fun/rtap.tm.dendrite/dot_general:",
     "rtap.tm.dendrite"),
    ("jit(chunk_step)/while:", "unscoped"), ("", "unscoped"),
    ("jit(f)/rtap.reduce.health/reduce_sum", "rtap.reduce.health")])
def test_scope_of(op_name, scope):
    assert st.scope_of(op_name) == scope


def test_phase_ms_exact():
    planes = hand_made()
    assert st.phase_ms(planes, "rtap.group.stage", "chunk") == \
        pytest.approx((10 + 30 + 20 + 40) / 4 / 1e6)
    # two groups in lockstep: four events are two ticks
    assert st.phase_ms(planes, "rtap.group.stage", "tick") == \
        pytest.approx((10 + 30 + 20 + 40) / 2 / 1e6)
    # an annotation the window cuts is left out; none at all reads None
    assert st.phase_ms(planes, "rtap.group.fetch", "chunk", (50, 1650)) is None
    assert st.phase_ms(planes, "rtap.group.likelihood", "chunk") is None
    with pytest.raises(ValueError, match="unknown 'per'"):
        st.phase_ms(planes, "rtap.group.stage", "row")


# ---- the .xplane.pb reader, on a file encoded here field by field ----

def _vi(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(field, value):
    """One protobuf field: int -> varint, bytes/str -> length-delimited,
    float -> 64-bit."""
    if isinstance(value, int):
        return _vi(field << 3) + _vi(value)
    if isinstance(value, float):
        return _vi(field << 3 | 1) + struct.pack("<d", value)
    raw = value.encode() if isinstance(value, str) else value
    return _vi(field << 3 | 2) + _vi(len(raw)) + raw


def _entry(key, message):
    return _f(1, key) + _f(2, message)


def _xplane(tmp_path):
    tf_op, group, seq, gname = 1, 2, 3, 4
    stat_names = b"".join(
        _f(5, _entry(i, _f(1, i) + _f(2, n))) for i, n in
        ((tf_op, "tf_op"), (group, "group"), (seq, "seq"), (gname, "node7.m0")))
    device = (
        _f(2, "/device:TPU:0")
        + _f(4, _entry(1, _f(2, "jit_step(77)")))
        + _f(4, _entry(2, _f(2, "%fusion.3 = pred[8]{0} fusion(%a)")
                       + _f(5, _f(1, tf_op) + _f(5, SP))))
        + _f(4, _entry(3, _f(2, "%copy.1 = s16[8]{0} copy(%p)")))
        + stat_names
        + _f(3, _f(2, "XLA Modules") + _f(3, 1000)
             + _f(4, _f(1, 1) + _f(2, 5_000_000) + _f(3, 300_000_000)))
        + _f(3, _f(2, "XLA Ops") + _f(3, 1000)
             + _f(4, _f(1, 3) + _f(2, 5_000_000) + _f(3, 40_000_000))
             + _f(4, _f(1, 2) + _f(2, 45_000_000) + _f(3, 250_000_500)))
        + _f(3, _f(2, "Async XLA Ops") + _f(4, _f(1, 3) + _f(2, 1) + _f(3, 1))))
    host = (
        _f(2, "/host:CPU") + stat_names
        + _f(4, _entry(1, _f(2, "rtap.group.fetch")))
        + _f(4, _entry(2, _f(2, "bench_sync")))
        + _f(4, _entry(3, _f(2, "PjitFunction(chunk_step)")))
        + _f(3, _f(2, "python") + _f(3, 0)
             + _f(4, _f(1, 2) + _f(2, 900_000) + _f(3, 1_000_000))
             + _f(4, _f(1, 3) + _f(2, 2_000_000) + _f(3, 1_000_000))
             + _f(4, _f(1, 1) + _f(2, 7_000_000) + _f(3, 2_500_000)
                  + _f(4, _f(1, group) + _f(7, gname))
                  + _f(4, _f(1, seq) + _f(4, 12)))))
    other = _f(2, "/host:metadata") + _f(4, _entry(1, _f(2, "jit_step(77)")))
    d = tmp_path / "log" / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        _f(1, device) + _f(1, host) + _f(1, other))
    return str(tmp_path / "log")


def test_load_reads_op_names_from_event_metadata(tmp_path):
    planes = st.load(_xplane(tmp_path))
    assert planes == {
        "/device:TPU:0": {
            # line timestamp (ns) + offset (ps); durations in ps
            "XLA Modules": [["jit_step(77)", 6000.0, 300000.0]],
            "XLA Ops": [["%copy.1 = s16[8]{0} copy(%p)", 6000.0, 40000.0, ""],
                        ["%fusion.3 = pred[8]{0} fusion(%a)", 46000.0,
                         250000.5, SP]]},
        "/host:CPU": {"annotations": [
            ["bench_sync", 900.0, 1000.0, {}],
            ["rtap.group.fetch", 7000.0, 2500.0,
             {"group": "node7.m0", "seq": 12}]]}}
    with pytest.raises(FileNotFoundError):
        st.load(str(tmp_path / "nope"))


def test_load_reads_a_trace_this_jaxlib_wrote(tmp_path):
    # the host plane of a real (CPU) profile: annotations with their
    # keyword arguments, through the same field numbers
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench_sync"):
            pass
        with jax.profiler.TraceAnnotation("rtap.group.stage", group="g0",
                                          seq=3):
            pass
        with jax.profiler.TraceAnnotation("not.ours", seq=4):
            pass
    finally:
        jax.profiler.stop_trace()
    events = st.load(str(tmp_path))["/host:CPU"]["annotations"]
    assert [(n, a) for n, _s, _d, a in events] == [
        ("bench_sync", {}), ("rtap.group.stage", {"group": "g0", "seq": 3})]
    assert events[0][1] <= events[1][1] and events[1][2] >= 0


def test_newest_log_dir_is_the_cell_traced_last(tmp_path):
    assert st.newest_log_dir(str(tmp_path)) is None
    for i, cell in enumerate(("cell-a", "cell-b")):
        d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "h.xplane.pb").write_bytes(b"")
        os.utime(d / "h.xplane.pb", (1000 + i, 1000 + i))
    assert st.newest_log_dir(str(tmp_path)) == \
        str(tmp_path / ".bench_trace" / "cell-b")


# ---- bytes from shapes ----

@pytest.mark.parametrize("name", ["cluster-256", "cluster-32"])
def test_kernel_bytes_from_shapes(name):
    m = model(name)["model"]
    leaves = leaf_bytes(m)
    # where the leaves coincide with roofline.py's: equal to the byte
    assert sum(leaves[k] for k in STATE_LEAVES) == state_bytes_per_stream(m)
    C, n_in = m["sp"]["columns"], m["rdse"]["size"]
    P = round(n_in * m["sp"]["potential_pct"])
    assert kernel_bytes_per_stream("rtap.sp.overlap", m) == \
        C * P * 2 + C * P * 2 + n_in + C * 4  # members, perm, SDR; overlap
    for scope, (read, written) in KERNELS.items():
        assert set(read) | set(written) <= set(leaves), scope
        assert kernel_bytes_per_stream(scope, m) < \
            2 * state_bytes_per_stream(m)  # no kernel moves the whole state twice
    assert kernel_floor_seconds("rtap.tm", m, 1024, "TPU v5 lite") == \
        pytest.approx(kernel_bytes_per_stream("rtap.tm", m) * 1024 / 819e9)
    with pytest.raises(KeyError, match="no byte count"):
        kernel_bytes_per_stream("rtap.encode", m)


# ---- the recording of this PR's chip runs ----

def _top_op(planes, scope):
    time = {}
    for text, _s, d, op_name in planes["/device:TPU:0"]["XLA Ops"]:
        if st.scope_of(op_name) == scope:
            op = text.split(" = ")[0]
            time[op] = time.get(op, 0) + d
    return max(time, key=time.get)


def test_recorded_replay_trace(recorded):
    rec = recorded["cluster-256-replay"]
    planes, T = rec["planes"], rec["chunk_ticks"]
    clipped, whole = planes["/device:TPU:0"]["XLA Modules"]
    assert clipped[2] < 0.9 * whole[2]  # the tracer cut the first program
    table = st.by_scope(planes, "jit_chunk_step", T)
    # the clipped program is left out: per tick, the whole one's time alone
    assert sum(table.values()) == pytest.approx(whole[2] / T / 1e6, rel=2e-3)
    assert table["rtap.sp.overlap"] == pytest.approx(173.0, abs=0.1)
    assert table["rtap.tm.learn"] == pytest.approx(21.95, abs=0.1)
    assert table["rtap.tm.dendrite"] == pytest.approx(3.12, abs=0.02)
    assert 0 < table["unscoped"] < 0.05 * sum(table.values())
    assert table["rtap.sp.overlap"] > 0.85 * sum(table.values())
    # per chunk: the annotations of the three chunks in the recording
    assert st.phase_ms(planes, "rtap.group.stage", "chunk") == \
        pytest.approx(0.757, abs=0.001)
    assert st.phase_ms(planes, "rtap.group.likelihood", "chunk") == \
        pytest.approx(0.857, abs=0.001)
    seqs = {}
    for name, _s, _d, args in planes["/host:CPU"]["annotations"]:
        if name.startswith("rtap.group."):
            seqs.setdefault((args["group"], args["seq"]), set()).add(name)
    # one chunk's phases share (group, seq)
    assert any(len(v) == 4 for v in seqs.values())


def test_scope_survives_xlas_renumbering(recorded):
    replay = recorded["cluster-256-replay"]["planes"]
    live = recorded["cluster-256-live"]["planes"]
    # two programs of the same kernels: XLA's name for the SP gather differs
    assert _top_op(replay, "rtap.sp.overlap") == "%fusion.211"
    assert _top_op(live, "rtap.sp.overlap") == "%fusion"
    a = st.by_scope(replay, "jit_chunk_step", 8)
    b = st.by_scope(live, "jit_chunk_step", 1)
    assert a["rtap.sp.overlap"] == pytest.approx(b["rtap.sp.overlap"], rel=2e-3)
    assert set(a) == set(b)


def test_recorded_live_trace_per_tick(recorded):
    planes = recorded["cluster-256-live"]["planes"]
    groups = {a["group"] for n, _s, _d, a in planes["/host:CPU"]["annotations"]
              if n == "rtap.group.fetch"}
    assert len(groups) == 16
    # two ticks of sixteen groups: the fetch is the wait for sixteen programs
    assert st.phase_ms(planes, "rtap.group.fetch", "tick") == \
        pytest.approx(3221.4, abs=0.1)
    assert st.phase_ms(planes, "rtap.group.likelihood", "tick") == \
        pytest.approx(4.61, abs=0.01)


# ---- the readers, as the harness calls them ----

def _record(recorded, cell="cluster-256-replay"):
    rec = recorded[cell]
    return {"trace": {"window_s": 10.0}, "scoped_planes": rec["planes"],
            "chunk_ticks": rec["chunk_ticks"], "device_kind": "TPU v5 lite",
            "config": model("cluster-256")}


def test_readers_on_the_recording(recorded):
    reg = Registry()
    record = _record(recorded)
    values = {}
    for name in list(SCOPE_MS) + list(ROOFLINES) + list(PHASES)[:4]:
        definition, reader = reg.layer_metric(name)
        values[name] = reader.read(record, definition)
    assert sum(values[n] for n in SCOPE_MS) == pytest.approx(201.13, abs=0.05)
    assert values["encode_ms.replay"] == 0.0  # no op of 5 us carries it
    floor = kernel_floor_seconds("rtap.sp.overlap", record["config"]["model"],
                                 1024, "TPU v5 lite")
    assert values["sp_overlap_roofline.replay"] == pytest.approx(
        100 * floor / (values["sp_overlap_ms.replay"] / 1e3))
    assert values["sp_overlap_roofline.replay"] == pytest.approx(0.0482, abs=2e-4)
    assert all(0 < values[n] < 100 for n in ROOFLINES)
    assert values["group_fetch_ms.replay"] == pytest.approx(0.822, abs=0.001)
    live = _record(recorded, "cluster-256-live")
    for name in list(PHASES)[4:]:
        definition, reader = reg.layer_metric(name)
        assert reader.read(live, definition) > 0


def test_readers_read_nothing_where_there_is_nothing(recorded):
    reg = Registry()
    untraced = {"trace": None}
    bare = _record(recorded)
    bare["scoped_planes"] = json.loads(json.dumps(bare["scoped_planes"]))
    for ev in bare["scoped_planes"]["/device:TPU:0"]["XLA Ops"]:
        ev[3] = ev[3].replace("rtap.", "")  # a program before the scopes
    bare["scoped_planes"]["/host:CPU"]["annotations"] = [
        a for a in bare["scoped_planes"]["/host:CPU"]["annotations"]
        if a[0] == "bench_sync"]
    for name in list(SCOPE_MS) + list(ROOFLINES) + list(PHASES):
        definition, reader = reg.layer_metric(name)
        assert reader.read(untraced, definition) is None
        assert reader.read(bare, definition) is None, name


def metric_files_resolve_and_name_their_cells(reg: Registry) -> None:
    replay = ["cluster-256-replay", "cluster-32-replay"]
    for name, scope in {**SCOPE_MS, **ROOFLINES}.items():
        listed = rules.entry(reg.manifest["per_layer"], name)
        definition = rules.agrees_with_definition(reg, listed)
        assert (definition["scope"], definition["module"]) == \
            (scope, "jit_chunk_step")
        # a later cell may be appended to a metric's list, never put before;
        # the SP overlap's share is read where HBM bounds the scope only (at
        # 32 columns the scan's carry holds its pools on chip: PERF.md s7)
        rules.starts_with(listed["workloads"], replay[:1] if name ==
                          "sp_overlap_roofline.replay" else replay)
        assert (listed["source"], listed["layer"]) == \
            ("device_trace", "kernels")
    for name, (phase, per) in PHASES.items():
        listed = rules.entry(reg.manifest["per_layer"], name)
        definition = rules.agrees_with_definition(reg, listed)
        assert (definition["phase"], definition["per"]) == (phase, per)
        rules.starts_with(listed["workloads"], replay if name.endswith(
            ".replay") else ["cluster-256-live"])
        assert (listed["source"], listed["layer"]) == \
            ("program_span", "stream groups")
    rules.added_in_order(reg.manifest["per_layer"], SCOPE_MS)
    assert len(SCOPE_MS) + len(ROOFLINES) + len(PHASES) == 17


manifest_holds = metric_files_resolve_and_name_their_cells


def test_the_new_metric_files_resolve_and_name_their_cells():
    metric_files_resolve_and_name_their_cells(Registry())
