"""Topology-aware incident correlation units (ISSUE 9 tentpole b):
TopologyMap spec parsing + inference + components, IncidentCorrelator
window edges / hysteresis / thresholds, and the crash-resume dedupe fold
over the shared alert-stream walker."""

import json

import pytest

from rtap_tpu.correlate import IncidentCorrelator, TopologyMap
from rtap_tpu.correlate.incidents import incident_id_of
from rtap_tpu.correlate.topology import (
    UNKNOWN_SERVICE,
    node_of_stream,
    service_of_node,
)
from rtap_tpu.obs.metrics import TelemetryRegistry

SPEC = {"services": {"web": ["web-00", "web-01"], "db": ["db-00"],
                     "batch": ["batch-00", "batch-01"]},
        "links": [["web", "db"]]}


def _correlator(**kw):
    kw.setdefault("topology", TopologyMap.from_spec(SPEC))
    kw.setdefault("window_s", 5)
    kw.setdefault("min_streams", 2)
    kw.setdefault("registry", TelemetryRegistry())
    return IncidentCorrelator(**kw)


class TestTopologyMap:
    def test_stream_and_node_parsing(self):
        assert node_of_stream("web-00.cpu") == "web-00"
        assert node_of_stream("a.b.cpu") == "a.b"
        assert node_of_stream("nodot") == "nodot"
        assert service_of_node("web-01") == "web"
        assert service_of_node("node00003") == "node"
        assert service_of_node("db2") == "db"
        assert service_of_node("12345") == "12345"  # all digits: own service

    @pytest.mark.quick
    def test_linked_services_share_a_cluster(self):
        topo = TopologyMap.from_spec(SPEC)
        assert topo.cluster_of("web-00.cpu") == topo.cluster_of("db-00.mem")
        assert topo.cluster_of("batch-00.cpu") != topo.cluster_of("web-00.cpu")
        assert topo.adjacent("web-01", "db-00")
        assert not topo.adjacent("batch-00", "db-00")

    def test_cluster_keys_are_deterministic(self):
        # canonical component name = lexicographically smallest member,
        # independent of declaration order
        spec2 = {"services": {"db": ["db-00"], "batch": ["batch-00"],
                              "web": ["web-00", "web-01"]},
                 "links": [["db", "web"]]}
        a = TopologyMap.from_spec(SPEC)
        b = TopologyMap.from_spec(spec2)
        assert a.cluster_of("web-00.cpu") == b.cluster_of("web-00.cpu") == "db"

    def test_cluster_keys_deterministic_across_hash_seeds(self):
        """ISSUE 13 replay-determinism pin: component keys must be
        byte-identical across PROCESSES, not just within one — CPython
        randomizes str hashes per process, so any surviving unsorted
        set iteration in _rebuild_components would diverge here."""
        import os
        import subprocess
        import sys

        prog = (
            "import json\n"
            "from rtap_tpu.correlate import TopologyMap\n"
            "spec = {'services': {chr(97 + i) * 3: ['n%d' % i]\n"
            "                     for i in range(12)},\n"
            "        'links': [[chr(97 + i) * 3, chr(98 + i) * 3]\n"
            "                  for i in range(0, 10, 2)]}\n"
            "t = TopologyMap.from_spec(spec)\n"
            "print(json.dumps(t._component, sort_keys=True))\n")
        outs = set()
        for seed in ("0", "1"):
            env = dict(os.environ, PYTHONHASHSEED=seed,
                       JAX_PLATFORMS="cpu")
            p = subprocess.run([sys.executable, "-c", prog], env=env,
                               capture_output=True, text=True,
                               timeout=120)
            assert p.returncode == 0, p.stderr
            outs.add(p.stdout.strip())
        assert len(outs) == 1, f"component map diverged: {outs}"

    def test_spec_accepts_json_string_and_rejects_bad_shapes(self):
        topo = TopologyMap.from_spec(json.dumps(SPEC))
        assert topo.cluster_of("db-00.x") == "db"
        with pytest.raises(ValueError, match="services"):
            TopologyMap.from_spec({"links": []})
        with pytest.raises(ValueError, match="node list"):
            TopologyMap.from_spec({"services": {"web": "web-00"}})
        with pytest.raises(ValueError, match="appears in services"):
            TopologyMap.from_spec(
                {"services": {"a": ["n0"], "b": ["n0"]}})
        with pytest.raises(ValueError, match="undeclared"):
            TopologyMap.from_spec(
                {"services": {"a": ["n0"]}, "links": [["a", "ghost"]]})

    def test_unknown_nodes_degrade_not_crash(self):
        topo = TopologyMap.from_spec(SPEC)
        # outside the spec: catch-all service, still correlates per node
        assert topo.service_of("mystery-07") == UNKNOWN_SERVICE
        assert topo.cluster_of("mystery-07.cpu") == UNKNOWN_SERVICE

    @pytest.mark.quick
    def test_inference_mode_groups_by_stripped_prefix(self):
        topo = TopologyMap.infer()
        assert topo.cluster_of("web-01.cpu") == topo.cluster_of("web-02.mem")
        assert topo.cluster_of("node00003.net") == \
            topo.cluster_of("node00009.cpu")
        assert topo.cluster_of("web-01.cpu") != topo.cluster_of("db-01.cpu")


class TestIncidentCorrelator:
    @pytest.mark.quick
    def test_one_incident_per_cluster_burst(self):
        out = []
        co = _correlator(sink=out.append)
        # linked web+db burst together; batch stays quiet
        co.observe_alert("a1", "web-00.cpu", 100)
        co.observe_alert("a2", "web-01.cpu", 101)
        co.observe_alert("a3", "db-00.mem", 103)
        for t in range(104, 110):
            co.on_tick(t)
        assert len(out) == 1
        inc = out[0]
        assert inc["event"] == "incident"
        assert inc["nodes"] == ["db-00", "web-00", "web-01"]
        assert inc["alert_ids"] == ["a1", "a2", "a3"]
        assert inc["onset_ts"] == 100 and inc["end_ts"] == 103
        assert inc["incident_id"] == incident_id_of(["a1", "a2", "a3"])

    def test_window_closes_on_quiescence_not_onset(self):
        """Hysteresis: a re-burst INSIDE the window extends the same
        incident instead of paging twice."""
        out = []
        co = _correlator(sink=out.append)
        co.observe_alert("a1", "web-00.cpu", 100)
        co.observe_alert("a2", "web-01.cpu", 101)
        co.on_tick(105)  # 4s after last member: window_s=5 not yet reached
        assert not out
        co.observe_alert("a3", "db-00.mem", 105)  # re-burst extends
        co.on_tick(110)
        assert not out
        co.on_tick(111)  # 6s after the re-burst: closes
        assert len(out) == 1 and out[0]["members"] == 3

    def test_window_edge_exact_boundary(self):
        """now - last == window_s holds the window; strictly greater
        closes it (the > in on_tick)."""
        out = []
        co = _correlator(sink=out.append)
        co.observe_alert("a1", "web-00.cpu", 100)
        co.observe_alert("a2", "web-01.cpu", 100)
        co.on_tick(105)
        assert not out
        co.on_tick(106)
        assert len(out) == 1

    def test_max_span_bounds_continuous_alerting(self):
        out = []
        co = _correlator(sink=out.append, max_span_s=10)
        for t in range(100, 140):  # a member EVERY tick: never quiesces
            co.observe_alert(f"a{t}", f"web-0{t % 2}.cpu", t)
            co.on_tick(t)
        assert out, "the hard span bound must force a close"
        assert out[0]["span_s"] <= 11

    def test_below_min_streams_expires_silently(self):
        out = []
        co = _correlator(sink=out.append, min_streams=3)
        co.observe_alert("a1", "web-00.cpu", 100)
        co.observe_alert("a2", "web-00.cpu", 101)  # same stream twice
        co.observe_alert("a3", "web-01.cpu", 102)  # 2 distinct < 3
        for t in range(103, 112):
            co.on_tick(t)
        assert not out
        assert co.stats()["windows_expired"] == 1

    def test_distinct_clusters_page_separately(self):
        out = []
        co = _correlator(sink=out.append)
        co.observe_alert("a1", "web-00.cpu", 100)
        co.observe_alert("a2", "db-00.cpu", 100)   # same cluster (linked)
        co.observe_alert("b1", "batch-00.cpu", 100)
        co.observe_alert("b2", "batch-01.cpu", 100)
        for t in range(101, 108):
            co.on_tick(t)
        assert len(out) == 2
        assert {o["cluster"] for o in out} == {"batch", "db"}

    def test_validation(self):
        with pytest.raises(ValueError, match="window_s"):
            _correlator(window_s=0)
        with pytest.raises(ValueError, match="min_streams"):
            _correlator(min_streams=1)
        with pytest.raises(ValueError, match="max_span_s"):
            _correlator(window_s=30, max_span_s=5)

    def test_incident_id_is_content_derived(self):
        assert incident_id_of(["b", "a"]) == incident_id_of(["a", "b"])
        assert incident_id_of(["a"]) != incident_id_of(["b"])

    def test_large_blast_requests_flight_dump(self):
        dumps = []

        class Flight:
            def request_dump(self, reason, tick):
                dumps.append((reason, tick))

        co = _correlator(sink=lambda _r: None, flight=Flight(),
                         blast_dump_nodes=3)
        for i, s in enumerate(("web-00.cpu", "web-01.mem", "db-00.cpu")):
            co.observe_alert(f"a{i}", s, 100 + i)
        for t in range(103, 110):
            co.on_tick(t, tick=t - 100)
        assert dumps and dumps[0][0] == "incident"


class TestResume:
    def _sink_file(self, tmp_path, lines):
        p = tmp_path / "alerts.jsonl"
        p.write_text("".join(json.dumps(d) + "\n" for d in lines))
        return str(p)

    def _alert(self, aid, stream, ts):
        return {"alert_id": aid, "stream": stream, "ts": ts}

    @pytest.mark.quick
    def test_already_emitted_incident_dedupes(self, tmp_path):
        """The event line landed pre-crash: the re-fold must NOT re-emit
        (exactly-once across kill-9)."""
        alerts = [self._alert("a1", "web-00.cpu", 100),
                  self._alert("a2", "web-01.cpu", 101)]
        inc = {"event": "incident",
               "incident_id": incident_id_of(["a1", "a2"]),
               "alert_ids": ["a1", "a2"]}
        path = self._sink_file(tmp_path, alerts + [inc])
        out = []
        co = _correlator(sink=out.append)
        summary = co.resume_from(path)
        assert summary["alerts_refolded"] == 2
        co.on_tick(200)  # well past the window: the re-folded window closes
        assert not out, "a pre-crash-emitted incident must not re-emit"
        assert co.stats()["resume_deduped"] == 1

    @pytest.mark.quick
    def test_unemitted_closed_incident_re_emits(self, tmp_path):
        """The window closed pre-crash but its event line never landed:
        the resume fold must emit it exactly once."""
        alerts = [self._alert("a1", "web-00.cpu", 100),
                  self._alert("a2", "web-01.cpu", 101),
                  # a much later alert: drives the scan clock past the
                  # window close while still replaying
                  self._alert("z9", "batch-00.cpu", 400)]
        path = self._sink_file(tmp_path, alerts)
        out = []
        co = _correlator(sink=out.append)
        summary = co.resume_from(path)
        assert summary["re_emitted"] == 1
        assert len(out) == 1
        assert out[0]["alert_ids"] == ["a1", "a2"]

    def test_open_window_survives_crash_and_extends_live(self, tmp_path):
        """The hard case the workload soak kills into: the correlator
        dies MID-FOLD (window open, no incident line on disk). The
        resume re-folds the delivered members from the sink tail —
        replayed duplicates are suppressed upstream by the AlertWriter,
        so they re-enter from disk exactly once — and a post-resume
        member extends the SAME window: one incident, identical to the
        uninterrupted run's."""
        alerts = [self._alert("a1", "web-00.cpu", 100),
                  self._alert("a2", "web-01.cpu", 101)]
        path = self._sink_file(tmp_path, alerts)
        out = []
        co = _correlator(sink=out.append, min_streams=3)
        co.resume_from(path)
        assert not out, "an open window must not close during resume"
        co.observe_alert("a3", "db-00.mem", 103)  # the fault continues
        for t in range(104, 110):
            co.on_tick(t)
        assert len(out) == 1
        assert out[0]["alert_ids"] == ["a1", "a2", "a3"]
        assert out[0]["incident_id"] == incident_id_of(["a1", "a2", "a3"])

    def test_missing_file_is_an_empty_stream(self, tmp_path):
        co = _correlator()
        summary = co.resume_from(str(tmp_path / "nope.jsonl"))
        assert summary["scanned"] == 0

    def test_torn_tail_is_skipped(self, tmp_path):
        path = tmp_path / "alerts.jsonl"
        path.write_text(
            json.dumps(self._alert("a1", "web-00.cpu", 100)) + "\n"
            + '{"alert_id": "torn-by-kil')
        co = _correlator()
        summary = co.resume_from(str(path))
        assert summary["alerts_refolded"] == 1


@pytest.mark.quick
def test_correlator_fold_overhead_within_one_percent_of_tick_budget():
    """The CI twin of the python -m rtap_tpu.obs.selfbench bar: even at the
    alert-storm ceiling (a full blast radius folding every tick with
    every cluster window open) the correlator stays host-noise."""
    from rtap_tpu.obs.selfbench import measure_correlate

    res = measure_correlate(n=300)
    assert res["per_tick_overhead_frac"] <= 0.01, res


@pytest.mark.quick
def test_fields_ranked_by_member_count():
    """Incident `fields` order = attribution count desc, then name (the
    most-implicated field leads the triage list)."""
    got = []
    co = _correlator(sink=got.append, min_streams=2)
    tf_v = [{"name": "value", "contribution": 1.0, "bucket_delta": 3}]
    tf_e = [{"name": "event_class", "contribution": 1.0, "bucket_delta": 1}]
    co.observe_alert("a1", "web-00.cpu", 100, top_fields=tf_v)
    co.observe_alert("a2", "web-01.cpu", 100, top_fields=tf_v)
    co.observe_alert("a3", "web-01.mem", 100, top_fields=tf_e)
    co.on_tick(200)
    assert got and got[0]["fields"] == ["value", "event_class"]


@pytest.mark.quick
def test_snapshot_is_safe_against_concurrent_folds():
    """GET /incidents reads from the obs HTTP thread while the loop
    thread folds/closes: hammer both for a moment — no 'dict changed
    size during iteration' (the correlator lock)."""
    import threading

    co = _correlator(sink=lambda _r: None, min_streams=2)
    stop = threading.Event()
    errors = []

    def folder():
        t = 0
        while not stop.is_set():
            t += 1
            co.observe_alert(f"a{t}", f"w{t % 17}-00.cpu", t)
            co.on_tick(t + (100 if t % 5 == 0 else 0))

    th = threading.Thread(target=folder, daemon=True)
    th.start()
    try:
        import time
        deadline = time.time() + 0.4
        while time.time() < deadline:
            try:
                snap = co.snapshot()
                assert "open_windows" in snap
            except RuntimeError as e:  # pragma: no cover - the regression
                errors.append(e)
                break
    finally:
        stop.set()
        th.join(timeout=5)
    assert not errors, errors


@pytest.mark.quick
def test_dropped_alert_batches_never_fold(tmp_path):
    """A batch the sink refused (breaker open / fence lost) must NOT
    seed correlation windows: the fold mirrors the DISK (the resume
    re-fold's source of truth), so incident member ids always reference
    lines that exist on the stream."""
    import numpy as np

    from rtap_tpu.service.alerts import AlertWriter

    co = _correlator(sink=lambda _r: None, min_streams=2)
    fenced = {"ok": True}
    w = AlertWriter(path=str(tmp_path / "a.jsonl"),
                    fence=lambda: fenced["ok"], correlator=co)
    ll = np.array([0.9, 0.9], np.float32)
    w.emit_batch(["web-00.cpu", "web-01.cpu"], np.array([100, 100]),
                 np.array([1.0, 1.0]), np.array([0.9, 0.9], np.float32),
                 ll, ll >= 0.5, group=0, tick=1)
    assert co.correlated == 2  # delivered batch folds
    fenced["ok"] = False  # lease lost: the sink refuses the batch
    w.emit_batch(["web-00.cpu", "web-01.cpu"], np.array([101, 101]),
                 np.array([1.0, 1.0]), np.array([0.9, 0.9], np.float32),
                 ll, ll >= 0.5, group=0, tick=2)
    assert w.fenced_drops == 2
    assert co.correlated == 2  # refused lines never entered a window


@pytest.mark.quick
def test_topology_workload_rejects_cascade_past_stream_end():
    from rtap_tpu.data.synthetic import (
        SyntheticStreamConfig,
        generate_topology_workload,
    )

    with pytest.raises(ValueError, match="cascade does not fit"):
        generate_topology_workload(
            nodes_per_service=40, cascade_lag=3, burst_at_frac=0.75,
            cfg=SyntheticStreamConfig(length=400, n_anomalies=0))


@pytest.mark.quick
def test_open_windows_gauge_refreshes_on_expired_close():
    """An expired-below-threshold close must refresh the gauge even
    while other windows stay open (operators read it against
    min-streams tuning — TELEMETRY.md)."""
    co = _correlator(sink=lambda _r: None, min_streams=3)
    co.observe_alert("a1", "web-00.cpu", 100)   # cluster web+db (linked)
    co.observe_alert("a2", "batch-00.cpu", 103) # cluster batch
    assert co._obs_open.value == 2
    # web quiesces (2 streams < 3: expires silently); batch stays open
    co.observe_alert("a3", "batch-00.mem", 106)
    co.on_tick(106)
    assert co.expired == 1
    assert co._obs_open.value == 1


@pytest.mark.quick
def test_storm_cap_still_tracks_blast_radius(monkeypatch):
    """Past MAX_MEMBERS_PER_WINDOW, member ids are counted-not-stored —
    but streams/nodes/fields keep accumulating (bounded by fleet size),
    so min_streams decisions and blast_dump_nodes triggers never
    under-count in a fleet-wide storm."""
    import rtap_tpu.correlate.incidents as mod

    monkeypatch.setattr(mod, "MAX_MEMBERS_PER_WINDOW", 2)
    got = []
    co = _correlator(sink=got.append, min_streams=3)
    co.observe_alert("a1", "web-00.cpu", 100)
    co.observe_alert("a2", "web-00.mem", 100)
    co.observe_alert("a3", "web-01.cpu", 100)  # past the cap
    co.on_tick(200)
    assert got and got[0]["members_dropped"] == 1
    assert got[0]["streams"] == ["web-00.cpu", "web-00.mem", "web-01.cpu"]
    assert got[0]["nodes"] == ["web-00", "web-01"]


class TestResumeSidecar:
    """The <alerts>.corr floor: a checkpoint cursor PAST an open
    window's earlier members must not shrink the re-folded member set
    (the content-hash incident_id would diverge)."""

    def _alert_line(self, aid, stream, ts):
        return json.dumps({"alert_id": aid, "stream": stream, "ts": ts,
                           "value": 1.0, "raw_score": 0.9,
                           "log_likelihood": 0.9}) + "\n"

    def test_refold_from_sidecar_reproduces_incident_id(self, tmp_path):
        sink = tmp_path / "alerts.jsonl"
        side = str(sink) + ".corr"
        # live run: two members fold while the window is open; a
        # checkpoint saves with its alert cursor at EOF (past both)
        got = []
        live = _correlator(sink=got.append, min_streams=2,
                           sidecar_path=side)
        off = 0
        with open(sink, "w") as f:
            for aid, stream, ts in (("0:web-00.cpu:5", "web-00.cpu", 100),
                                    ("0:web-01.cpu:6", "web-01.cpu", 101)):
                line = self._alert_line(aid, stream, ts)
                live.observe_alert(aid, stream, ts, sink_offset=off)
                f.write(line)
                off += len(line)
        cursor = off  # the checkpoint's alerts_offset: past both members
        # reference: the uninterrupted run closes the window later
        ref_id = None
        live2 = _correlator(sink=got.append, min_streams=2)
        live2.observe_alert("0:web-00.cpu:5", "web-00.cpu", 100)
        live2.observe_alert("0:web-01.cpu:6", "web-01.cpu", 101)
        live2.on_tick(200)
        ref_id = got[-1]["incident_id"]
        # crash here. Resume: the sidecar floor (0, before member 1)
        # must win over the cursor — the re-fold reconstructs the FULL
        # member set and hashes the reference id
        res = []
        resumed = _correlator(sink=res.append, min_streams=2,
                              sidecar_path=side)
        start = resumed.resume_scan_offset(cursor)
        assert start == 0  # sidecar floor beats the cursor
        resumed.resume_from(str(sink), start)
        resumed.on_tick(200)
        assert res and res[-1]["incident_id"] == ref_id
        # the buggy pre-sidecar behavior (scan from the cursor) would
        # have re-folded nothing and emitted no/other incident

    def test_sidecar_advances_when_all_windows_close(self, tmp_path):
        side = str(tmp_path / "a.jsonl.corr")
        co = _correlator(sink=lambda _r: None, min_streams=2,
                         sidecar_path=side)
        co.observe_alert("a1", "web-00.cpu", 100, sink_offset=40)
        assert json.load(open(side))["offset"] == 40
        co.on_tick(200, sink_offset=777)  # window expires; none open
        assert json.load(open(side))["offset"] == 777
        assert co.resume_scan_offset(1000) == 777  # clamped to sidecar
        assert co.resume_scan_offset(500) == 500   # never past the cursor

    def test_refold_boundary_gap_matches_live_merge(self, tmp_path):
        """A member landing at a gap of EXACTLY window_s+1 merged live
        (a tick's alerts fold BEFORE its on_tick, so the last close
        check live made saw the previous second); the re-fold must
        reproduce that merge — advancing the scan clock to the member's
        own ts first would close the window early, expire it below
        min_streams, and lose the incident."""
        sink = tmp_path / "alerts.jsonl"
        sink.write_text(
            self._alert_line("0:web-00.cpu:1", "web-00.cpu", 100)
            + self._alert_line("0:web-01.cpu:2", "web-01.cpu", 106))
        # live: the gap-6 member (window_s=5) folds at tick 106 before
        # that tick's close check runs — ONE window, one incident
        got = []
        ref = _correlator(sink=got.append, min_streams=2)
        ref.observe_alert("0:web-00.cpu:1", "web-00.cpu", 100)
        ref.on_tick(105)  # the last close check before the fold: open
        ref.observe_alert("0:web-01.cpu:2", "web-01.cpu", 106)
        ref.on_tick(200)
        ref_id = got[-1]["incident_id"]
        # crash after the close: the re-fold must hash the same id
        res = []
        co = _correlator(sink=res.append, min_streams=2)
        co.resume_from(str(sink), 0)
        co.on_tick(200)
        assert res and res[-1]["incident_id"] == ref_id

    def test_resumed_window_anchors_floor_at_scan_start(self, tmp_path):
        """A window re-opened by the re-fold must anchor the sidecar
        floor at the scan start: a cluster opening LIVE afterwards (at a
        far-later sink offset) must not advance the persisted floor past
        the resumed window's earlier members — a second crash would
        re-fold a smaller member set and hash a divergent incident_id."""
        sink = tmp_path / "alerts.jsonl"
        side = str(sink) + ".corr"
        sink.write_text(
            self._alert_line("0:web-00.cpu:1", "web-00.cpu", 100)
            + self._alert_line("0:web-01.cpu:2", "web-01.cpu", 101))
        # reference: the uninterrupted run's full-member incident id
        got = []
        ref = _correlator(sink=got.append, min_streams=2)
        ref.observe_alert("0:web-00.cpu:1", "web-00.cpu", 100)
        ref.observe_alert("0:web-01.cpu:2", "web-01.cpu", 101)
        ref.on_tick(200)
        ref_id = got[-1]["incident_id"]
        # crash 1 -> resume: web's window re-opens during the scan
        co = _correlator(sink=lambda _r: None, min_streams=2,
                         sidecar_path=side)
        co.resume_from(str(sink), 0)
        # batch opens LIVE at a sink offset far past web's members
        co.observe_alert("0:batch-00.cpu:9", "batch-00.cpu", 102,
                         sink_offset=4096)
        assert json.load(open(side))["offset"] == 0  # web pins the floor
        # crash 2 while web is still open: the re-fold from the floor
        # rebuilds the FULL member set and hashes the reference id
        res2 = []
        co2 = _correlator(sink=res2.append, min_streams=2,
                          sidecar_path=side)
        start = co2.resume_scan_offset(10_000)
        assert start == 0
        co2.resume_from(str(sink), start)
        co2.on_tick(200)
        assert res2 and res2[-1]["incident_id"] == ref_id

    def test_missing_sidecar_scans_from_cursor(self, tmp_path):
        """No sidecar = no window ever opened under correlation: the
        scan starts at the checkpoints' cursor, NOT byte 0 — arming
        --topology on a sink with history must not re-fold (and page)
        every long-past burst at startup."""
        co = _correlator(sink=lambda _r: None,
                         sidecar_path=str(tmp_path / "nope.corr"))
        assert co.resume_scan_offset(12345) == 12345
        assert co.resume_scan_offset(-3) == 0

    def test_event_line_settles_cluster_mid_scan(self, tmp_path):
        """A pipeline-lagged alert whose ts sits just inside the window
        band must NOT merge into an already-closed window on re-fold:
        the incident event line pins the live closure point."""
        sink = tmp_path / "alerts.jsonl"
        got = []
        co = _correlator(sink=got.append, min_streams=2, window_s=5)
        lines = [self._alert_line("0:web-00.cpu:1", "web-00.cpu", 100),
                 self._alert_line("0:web-01.cpu:2", "web-01.cpu", 101)]
        inc_id = incident_id_of(["0:web-00.cpu:1", "0:web-01.cpu:2"])
        lines.append(json.dumps(
            {"event": "incident", "incident_id": inc_id, "cluster": "db",
             "members": 2,
             "alert_ids": ["0:web-00.cpu:1", "0:web-01.cpu:2"]}) + "\n")
        # lagged alert: ts 104 is within window_s of last_ts 101, but
        # live had already closed (tick clock ran ahead) — the event
        # line above is the proof
        lines.append(self._alert_line("0:web-00.mem:9", "web-00.mem", 104))
        sink.write_text("".join(lines))
        res = co.resume_from(str(sink), 0)
        assert res["incidents_known"] == 1
        # the lagged alert sits in a FRESH window (1 member), not merged
        snap = co.snapshot()
        assert list(snap["open_windows"].values())[0]["members"] == 1
        # and closing it stays below min_streams: no duplicate page
        co.on_tick(300)
        assert co.incidents == 0 and co.deduped == 0
