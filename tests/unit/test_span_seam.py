"""obs/trace.py's seam: ONE way for the served path to write a host span.

`span` reads the clock once at each end; that reading goes to a
TraceRecorder's ring (where the caller hands one) and brackets a
`jax.profiler.TraceAnnotation` named from `SPANS` (where a profiler trace is
running). The names are spelled out here on purpose — the benchmark's
readers match them in recorded traces, so a rename has to fail a test."""

import ast
import gc
import glob
import os
import socket
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from rtap_tpu.obs import trace as seam
from rtap_tpu.obs.trace import SPANS, TraceRecorder, span

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Profile:
    """A real CPU profiler trace around a block; `.events` afterwards:
    [(name, start_ns, dur_ns, {args})] of the host plane's `rtap.*`."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self.events = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("rtap."):
                        self.events.append((ev.name, ev.start_ns,
                                            ev.duration_ns, dict(ev.stats)))
        self.events.sort(key=lambda e: e[1])

    def named(self, name):
        return [e for e in self.events if e[0] == name]


def test_vocabulary():
    assert SPANS == (
        "rtap.loop.tick", "rtap.loop.source", "rtap.loop.membership",
        "rtap.loop.dispatch", "rtap.loop.collect", "rtap.loop.emit",
        "rtap.loop.alert", "rtap.loop.health", "rtap.loop.predict",
        "rtap.loop.checkpoint", "rtap.loop.sleep",
        "rtap.loop.group.dispatch", "rtap.loop.group.collect",
        "rtap.group.stage", "rtap.group.enqueue", "rtap.group.fetch",
        "rtap.group.likelihood",
        "rtap.ingest.feed", "rtap.ingest.snapshot",
        "rtap.aot.warm", "rtap.host.gc", "rtap.state.relayout",
        "rtap.checkpoint.save", "rtap.checkpoint.load")
    # the ring keeps the names benchmark/traffic_kinds/live.py reads
    ring = seam._RING_NAME
    assert [ring["rtap.loop." + n] for n in (
        "tick", "source", "membership", "dispatch", "collect", "emit",
        "checkpoint")] == ["tick", "source", "membership", "dispatch",
                           "collect", "emit", "checkpoint"]
    assert ring["rtap.loop.group.dispatch"] == "dispatch"
    assert ring["rtap.loop.group.collect"] == "collect"
    assert ring["rtap.aot.warm"] == "aot_warm"
    assert ring["rtap.host.gc"] == "gc"
    assert ring["rtap.loop.alert"] == "alert"
    assert ring["rtap.loop.health"] == "health"
    assert ring["rtap.loop.predict"] == "predict"
    assert ring["rtap.checkpoint.save"] == "checkpoint_save"
    assert ring["rtap.checkpoint.load"] == "checkpoint_load"


def test_the_vocabulary_is_what_the_package_writes():
    """Every `rtap.*` name a call under rtap_tpu/ opens is a device scope
    (ops/step.py:SCOPES), the documented `rtap.sync` mark, or one of SPANS —
    and every name of SPANS is opened somewhere. No second mechanism writes
    host annotations: `TraceAnnotation` is named, in code, by the seam and
    by `serve --jax-trace`'s sync mark only."""
    from rtap_tpu.ops.step import SCOPES

    opened, annotation_sites = set(), set()
    for root, _dirs, files in os.walk(os.path.join(REPO, "rtap_tpu")):
        for name in files:
            # _gate_canary*: files tests/unit/test_static_checks.py drops
            # into the package and removes, on another worker meanwhile
            if not name.endswith(".py") or name.startswith("_gate_canary"):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if "TraceAnnotation" in (  # `x.TraceAnnotation`, getattr's
                        getattr(node, "attr", None), getattr(node, "value", None)):
                    annotation_sites.add(os.path.relpath(path, REPO))
                if isinstance(node, ast.Call) and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str) \
                        and node.args[0].value.startswith("rtap."):
                    opened.add(node.args[0].value)
    assert opened - set(SCOPES) - {"rtap.sync"} == set(SPANS)
    assert annotation_sites == {"rtap_tpu/__main__.py",
                                "rtap_tpu/obs/trace.py"}


@pytest.mark.parametrize("mode", ["recorder", "profiler", "both", "neither"])
def test_seam_modes(mode, tmp_path):
    rec = TraceRecorder(capacity=64) if mode in ("recorder", "both") else None

    def work():
        with span("rtap.loop.collect", rec, tick=7):
            with span("rtap.loop.group.collect", rec, tick=7, group="s000",
                      seq=3, track=2):
                time.sleep(0.002)
        sp = span("rtap.ingest.feed", bytes=120).begin()
        return sp, sp.end(wait_us=5)

    if mode in ("profiler", "both"):
        with Profile(tmp_path) as prof:
            sp, t1 = work()
        (outer,) = prof.named("rtap.loop.collect")
        (inner,) = prof.named("rtap.loop.group.collect")
        (feed,) = prof.named("rtap.ingest.feed")
        assert outer[3] == {"tick": 7}
        assert inner[3] == {"tick": 7, "group": "s000", "seq": 3}
        # counts given at the start and at the end both arrive
        assert feed[3] == {"bytes": 120, "wait_us": 5}
        assert outer[1] <= inner[1] and \
            inner[1] + inner[2] <= outer[1] + outer[2]
        assert inner[2] >= 2e6
    else:
        sp, t1 = work()
        assert sp._ann is None
    assert t1 >= sp.t0  # the readings the caller books from
    if rec is None:
        return
    spans = rec.records()
    assert [(r["name"], r["tick"], r["group"]) for r in spans] == [
        ("collect", 7, -1), ("collect", 7, 2)]
    assert spans[1]["dur"] >= 0.002 and spans[0]["dur"] >= spans[1]["dur"]


def test_neither_allocates_nothing_that_stays():
    def once():
        with span("rtap.group.stage", group="s000", seq=1):
            pass

    once()
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in range(2000):
        once()
    assert sys.getallocatedblocks() - before < 50


def test_booked_duration_and_annotation_only():
    rec = TraceRecorder(capacity=64)
    sp = span("rtap.loop.membership", rec, tick=1).begin()
    sp.end(dur=0.25)  # the ring keeps what the loop booked
    sp = span("rtap.loop.membership", rec, tick=2).begin()
    sp.end(dur=0.0, record=False)  # nothing booked: no record
    with span("rtap.loop.sleep", tick=2):  # annotation only
        pass
    (only,) = rec.records()
    assert (only["name"], only["tick"], only["dur"]) == ("membership", 1, 0.25)


def test_the_oracle_backend_stays_off_jax():
    """The cpu-oracle serve path never loads JAX; the seam must not be what
    does (it looks JAX up in sys.modules and imports nothing)."""
    code = (
        "import sys, numpy as np\n"
        "from rtap_tpu.config import scaled_cluster_preset\n"
        "from rtap_tpu.obs.trace import TraceRecorder, span\n"
        "from rtap_tpu.service.registry import StreamGroup\n"
        "g = StreamGroup(scaled_cluster_preset(32), ['a0'], backend='cpu')\n"
        "g.run_chunk(np.ones((2, 1), np.float32),\n"
        "            np.full((2, 1), 1700000000, np.int64))\n"
        "rec = TraceRecorder(capacity=8)\n"
        "with span('rtap.loop.tick', rec, tick=0): pass\n"
        "assert rec.total == 1\n"
        "assert 'jax' not in sys.modules, 'the seam loaded JAX'\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_gc_hook_records_a_forced_collection_and_uninstalls(tmp_path):
    rec = TraceRecorder(capacity=256)
    seam.install_gc_hook()
    try:
        with Profile(tmp_path) as prof:
            gc.collect()
        marks = prof.named("rtap.host.gc")
        full = [m for m in marks if m[3]["generation"] == 2]
        assert full and all("collected" in m[3] for m in marks)
        ring = [r for r in rec.records() if r["name"] == "gc"]
        # loop track, filed under the newest tick the recorder has seen
        assert ring and all(r["tick"] == -1 and r["group"] == -1
                            for r in ring)
        rec.add_span("tick", 41, time.perf_counter(), 0.0)
        gc.collect()
        assert [r for r in rec.records() if r["name"] == "gc"][-1]["tick"] == 41
        assert '"generation": 2' in ring[-1]["args_json"]
        # the Chrome export carries the arguments
        ev = [e for e in rec.chrome_trace()["traceEvents"]
              if e["name"] == "gc"][-1]
        assert ev["ph"] == "X" and ev["args"]["generation"] == 2
        n = rec.total
        seam.uninstall_gc_hook()
        assert seam._on_gc not in gc.callbacks
        gc.collect()
        with span("rtap.loop.sleep"):  # use of the seam does not bring it back
            pass
        assert seam._on_gc not in gc.callbacks and rec.total == n
    finally:
        seam.install_gc_hook()
    assert gc.callbacks.count(seam._on_gc) == 1
    seam.install_gc_hook()  # idempotent
    assert gc.callbacks.count(seam._on_gc) == 1


def test_a_dead_recorder_takes_no_gc_spans():
    rec = TraceRecorder(capacity=8)
    assert rec in seam._RECORDERS
    n = len(seam._RECORDERS)
    del rec
    gc.collect()
    assert len(seam._RECORDERS) == n - 1


@pytest.mark.parametrize("n_fields", [1, 3], ids=["scalar", "vector"])
@pytest.mark.parametrize("native", [None, False], ids=["auto", "python"])
def test_ingest_feed_bytes_and_values_sum_to_what_was_sent(native, n_fields,
                                                           tmp_path):
    """`rtap.ingest.feed` says what each batch did — `bytes` taken, `values`
    written, `nulls` among them counted apart — and `rtap.ingest.snapshot`
    the record's width; at three fields every record's middle one is null."""
    from rtap_tpu.service.sources import TcpJsonlSource

    ids = [f"s{i:03d}" for i in range(50)]
    record = b'{"id": "%s", "value": %d.5, "ts": 1700000000}\n' \
        if n_fields == 1 else \
        b'{"id": "%s", "values": [%d.5, null, 7], "ts": 1700000000}\n'
    payload = b"".join(record % (sid.encode(), i)
                       for i, sid in enumerate(ids)) * 40
    with TcpJsonlSource(ids, native=native, n_fields=n_fields) as src:
        with Profile(tmp_path) as prof:
            with socket.create_connection(src.address) as conn:
                conn.sendall(payload)
            deadline = time.time() + 20
            while src.records_parsed < 50 * 40 and time.time() < deadline:
                time.sleep(0.01)
            values, _ts = src(3)
        assert src.records_parsed == 50 * 40 and src.parse_errors == 0
    first = values if n_fields == 1 else values[:, 0]
    assert np.array_equal(first, np.arange(50, dtype=np.float32) + 0.5)
    feeds = prof.named("rtap.ingest.feed")
    assert sum(f[3]["bytes"] for f in feeds) == len(payload)
    assert all(f[3]["wait_us"] >= 0 for f in feeds)
    nulls = 0 if n_fields == 1 else 50 * 40
    assert sum(f[3]["nulls"] for f in feeds) == nulls == src.values_null
    assert sum(f[3]["values"] for f in feeds) == src.values_parsed == \
        50 * 40 * n_fields - nulls
    (snap,) = prof.named("rtap.ingest.snapshot")
    assert snap[3]["tick"] == 3 and snap[3]["wait_us"] >= 0
    assert snap[3]["fields"] == n_fields


def test_python_fallback_feeds_an_unterminated_final_line():
    from rtap_tpu.service.sources import TcpJsonlSource

    with TcpJsonlSource(["a", "b"], native=False) as src:
        with socket.create_connection(src.address) as conn:
            conn.sendall(b'{"id": "a", "value": 1, "ts": 5}\n\n'
                         b'{"id": "b", "value": 2, "ts": 6}')
        deadline = time.time() + 20
        while src.records_parsed < 2 and time.time() < deadline:
            time.sleep(0.01)
        values, ts = src(0)
        # the blank line is a parse error, as it was with rfile's lines
        assert (src.records_parsed, src.parse_errors) == (2, 1)
    assert values.tolist() == [1.0, 2.0] and ts == 6


def _live_run(tmp_path, n_ticks=4):
    from rtap_tpu.config import scaled_cluster_preset
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    reg = StreamGroupRegistry(scaled_cluster_preset(32), group_size=2,
                              backend="tpu")
    for i in range(4):
        reg.add_stream(f"s{i}")
    reg.finalize()

    def feed(k):
        return np.full(4, 40.0 + k, np.float32), 1_700_000_000 + k

    rec = TraceRecorder(capacity=4096)
    live_loop(feed, reg, n_ticks=1, cadence_s=0.0)  # compile outside
    with Profile(tmp_path) as prof:
        stats = live_loop(feed, reg, n_ticks=n_ticks, cadence_s=0.02,
                          trace=rec, aot_warmup=True)
    assert stats["ticks"] == n_ticks
    return rec, prof, reg


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    return _live_run(tmp_path_factory.mktemp("seam_live"))


def test_one_clock_reading_serves_ring_and_annotation(live):
    """The same tick's `collect` span (ring, perf_counter) and
    `rtap.loop.collect` annotation (the profiler's clock) agree within
    50 us: durations, and the offset from their tick's start."""
    rec, prof, _reg = live
    ring = {(r["name"], r["tick"]): r for r in rec.records()
            if r["kind"] == "span" and r["group"] < 0 and r["tick"] >= 0}
    checked = 0
    for name in ("tick", "source", "dispatch", "collect", "emit"):
        for ev in prof.named("rtap.loop." + name):
            k = ev[3]["tick"]
            r, tick_r = ring[(name, k)], ring[("tick", k)]
            (tick_ev,) = [e for e in prof.named("rtap.loop.tick")
                          if e[3]["tick"] == k]
            assert abs(ev[2] / 1e9 - r["dur"]) < 50e-6, (name, k)
            assert abs((ev[1] - tick_ev[1]) / 1e9
                       - (r["t0"] - tick_r["t0"])) < 50e-6, (name, k)
            checked += 1
    assert checked == 5 * 4


def test_tick_group_seq_chain(live):
    """tick -> (group, seq) -> the chunk's four phases: the loop's per-group
    spans carry the group's FIRST STREAM ID and the handle's seq, as the
    `rtap.group.*` phases do; the ring keeps the group INDEX as its track."""
    rec, prof, reg = live
    firsts = [g.stream_ids[0] for g in reg.groups]
    for side, phases in (("dispatch", ("stage", "enqueue")),
                         ("collect", ("fetch", "likelihood"))):
        for ev in prof.named("rtap.loop.group." + side):
            args = ev[3]
            assert args["group"] in firsts and args["seq"] >= 1
            for phase in phases:
                (child,) = [e for e in prof.named("rtap.group." + phase)
                            if e[3] == {"group": args["group"],
                                        "seq": args["seq"]}]
                assert ev[1] <= child[1] and \
                    child[1] + child[2] <= ev[1] + ev[2]
        tracks = {r["group"] for r in rec.records()
                  if r["name"] == side and r["group"] >= 0}
        assert tracks == {0, 1}
    # the cadence wait has a name in the profiler's trace and none in the ring
    assert len(prof.named("rtap.loop.sleep")) == 3
    assert not [r for r in rec.records() if r["name"] == "sleep"]


def test_children_cover_the_tick(live):
    _rec, prof, _reg = live
    for tick_ev in prof.named("rtap.loop.tick"):
        k = tick_ev[3]["tick"]
        covered = sum(
            e[2] for n in ("source", "membership", "dispatch", "collect",
                           "emit", "checkpoint")
            for e in prof.named("rtap.loop." + n) if e[3]["tick"] == k)
        assert covered <= tick_ev[2]
        assert covered >= 0.5 * tick_ev[2]  # the chip's bar is 98 %: PERF.md


def test_aot_warm_spans_reach_the_ring(live):
    rec, _prof, _reg = live
    warm = [r for r in rec.records() if r["name"] == "aot_warm"]
    # one program: chunk length 1, one config, learn on (no free slot: no
    # claim program)
    assert len(warm) == 1
    assert warm[0]["tick"] == -1 and warm[0]["group"] == -1
    assert warm[0]["dur"] > 0


def test_armed_loop_names_its_health_and_predict_folds(tmp_path):
    """`serve --health --predict`: one `rtap.loop.health` and one
    `rtap.loop.predict` span a tick, inside `rtap.loop.emit` and after
    `rtap.loop.alert`; the predict span says what the tick emitted, and the
    tracker's tallies reach the registry once a tick. An unarmed loop (the
    `live` fixture) writes neither."""
    from rtap_tpu.config import scaled_cluster_preset
    from rtap_tpu.obs.health import HealthTracker
    from rtap_tpu.obs.metrics import TelemetryRegistry
    from rtap_tpu.predict import PredictTracker
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    cfg = scaled_cluster_preset(32)
    reg = StreamGroupRegistry(cfg, group_size=2, backend="tpu", health=True,
                              predict=2)
    for i in range(4):
        reg.add_stream(f"s{i}")
    reg.finalize()
    rng = np.random.Generator(np.random.Philox(key=(51, 7)))
    rows = (10 + 80 * rng.random((14, 4))).astype(np.float32)

    def feed(k):
        return rows[k], 1_700_000_000 + k

    obs = TelemetryRegistry()
    events = []
    tracker = PredictTracker(2, registry=obs, sink=events.append, min_ticks=2,
                             warmup_ticks=1)
    health = HealthTracker(cfg, registry=obs)
    rec = TraceRecorder(capacity=4096)
    live_loop(feed, reg, n_ticks=1, cadence_s=0.0, predictor=tracker,
              health=health)  # compile outside
    n = 12
    with Profile(tmp_path) as prof:
        live_loop(lambda k: feed(k + 1), reg, n_ticks=n, cadence_s=0.01,
                  trace=rec, predictor=tracker, health=health)
    for name in ("health", "predict"):
        evs = prof.named("rtap.loop." + name)
        assert sorted(e[3]["tick"] for e in evs) == list(range(n)), name
        ring = [r for r in rec.records() if r["name"] == name]
        assert len(ring) == n and all(r["group"] == -1 for r in ring)
        for ev in evs:
            k = ev[3]["tick"]
            (emit,) = [e for e in prof.named("rtap.loop.emit")
                       if e[3]["tick"] == k]
            (alert,) = [e for e in prof.named("rtap.loop.alert")
                        if e[3]["tick"] == k]
            assert alert[1] + alert[2] <= ev[1]
            assert emit[1] <= ev[1] and ev[1] + ev[2] <= emit[1] + emit[2]
    fired = sum(e[3]["precursors"] for e in prof.named("rtap.loop.predict"))
    assert fired == sum(e["event"] == "precursor" for e in events) > 0
    assert all(e[3]["incidents"] == 0
               for e in prof.named("rtap.loop.predict"))  # no fuser armed
    # the tallies, mirrored once a tick
    assert obs.counter("rtap_obs_predict_streams_scored_total").value \
        == tracker.streams_scored > 0
    assert obs.counter("rtap_obs_predict_events_suppressed_total").value == 0
    assert obs.counter("rtap_obs_predict_events_total",
                       event="precursor").value == fired


def test_unarmed_loop_writes_neither_fold_span(live):
    rec, prof, _reg = live
    assert not prof.named("rtap.loop.health")
    assert not prof.named("rtap.loop.predict")
    assert not [r for r in rec.records() if r["name"] in ("health", "predict")]
