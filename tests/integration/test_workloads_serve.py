"""ISSUE 9 integration: workload breadth end to end.

- serve CLI flag gates for the correlation/composite knobs (usage errors
  surface instantly, before backend init — the ingest/replication gate
  discipline);
- the tiny K=1 cascading-fault workload soak (scripts/workload_soak.py):
  one seeded multi-node burst -> exactly ONE cluster-level incident,
  identical across a kill-9 journal-replay resume;
- the new-modality scoring pipeline at miniature scale (categorical
  burst detection through replay_streams);
- ``GET /incidents`` on the obs server.

Named to sort after test_cli.py so the tier-1 870 s window's dot count
is untouched (ROADMAP verify note).
"""

import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV = {**os.environ, "RTAP_FORCE_CPU": "1"}


def run_cli(*args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "rtap_tpu", *args],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------- CLI flag gates
@pytest.mark.quick
def test_serve_rejects_correlate_knobs_without_topology():
    p = run_cli("serve", "--streams", "a", "--alerts", "/tmp/x.jsonl",
                "--correlate-window", "10")
    assert p.returncode == 2
    assert "--topology" in p.stderr
    p = run_cli("serve", "--streams", "a", "--alerts", "/tmp/x.jsonl",
                "--correlate-min-streams", "3")
    assert p.returncode == 2
    assert "--topology" in p.stderr


@pytest.mark.quick
def test_serve_rejects_topology_without_alerts():
    p = run_cli("serve", "--streams", "a", "--topology", "infer")
    assert p.returncode == 2
    assert "--alerts" in p.stderr


@pytest.mark.quick
def test_serve_rejects_degenerate_correlate_values():
    p = run_cli("serve", "--streams", "a", "--alerts", "/tmp/x.jsonl",
                "--topology", "infer", "--correlate-window", "0")
    assert p.returncode == 2 and "--correlate-window" in p.stderr
    p = run_cli("serve", "--streams", "a", "--alerts", "/tmp/x.jsonl",
                "--topology", "infer", "--correlate-min-streams", "1")
    assert p.returncode == 2 and "--correlate-min-streams" in p.stderr


@pytest.mark.quick
def test_serve_rejects_bad_topology_spec(tmp_path):
    bad = tmp_path / "topo.json"
    bad.write_text(json.dumps({"links": [["a", "b"]]}))  # no "services"
    p = run_cli("serve", "--streams", "a", "--alerts", "/tmp/x.jsonl",
                "--topology", str(bad))
    assert p.returncode == 2
    assert "bad --topology" in p.stderr


@pytest.mark.quick
def test_serve_rejects_topology_under_replication():
    p = run_cli("serve", "--streams", "a", "--alerts", "/tmp/x.jsonl",
                "--topology", "infer", "--replicate-to", "h:1",
                "--journal-dir", "/tmp/j", "--lease-file", "/tmp/l",
                "--checkpoint-dir", "/tmp/ck")
    assert p.returncode == 2
    assert "replication" in p.stderr


@pytest.mark.quick
def test_serve_rejects_columns_on_composite_presets():
    for preset in ("composite", "categorical"):
        p = run_cli("serve", "--streams", "a", "--preset", preset,
                    "--columns", "32")
        assert p.returncode == 2
        assert "cluster preset only" in p.stderr


# ------------------------------------------- the cascading-fault soak
def test_workload_soak_one_kill_one_incident(tmp_path):
    """K=1 smoke of the acceptance soak: the seeded cascade produces
    exactly one incident whose stream is identical across a kill-9
    resume; the soak's exit code IS the verdict (5 = violated)."""
    out = str(tmp_path / "report.json")
    env = dict(ENV)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "workload_soak.py"),
         "--seed", "3", "--kills", "1", "--ticks", "180",
         "--cadence", "0.01", "--checkpoint-every", "12",
         "--backend", "cpu", "--workdir", str(tmp_path / "w"),
         "--out", out],
        env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, \
        f"workload soak rc={proc.returncode}\n{proc.stderr[-3000:]}"
    report = json.load(open(out))
    assert report["verified"], report["failures"]
    assert report["incidents_reference"] == 1
    assert report["incidents_crash_run"] == 1
    inc = report["incident"]
    assert sorted(inc["nodes"]) == sorted(report["burst_nodes"])
    assert inc["members"] >= 3


def test_chaos_topology_burst_pages_one_incident(tmp_path):
    """The --topology-burst chaos drill (ISSUE 9 satellite): a seeded
    correlated multi-group burst through the real chaos harness pages
    exactly ONE incident; exit code 5 = the verdict was violated."""
    out = str(tmp_path / "report.json")
    env = dict(ENV)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "chaos_soak.py"),
         "--seed", "1", "--topology-burst", "--backend", "cpu",
         "--cadence", "0.01", "--workdir", str(tmp_path / "w"),
         "--out", out],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, \
        f"topology-burst drill rc={proc.returncode}\n{proc.stderr[-3000:]}"
    report = json.load(open(out))
    assert report["verified"], report["failures"]
    assert report["incidents"] == 1
    assert report["incident"]["nodes"] == report["burst_nodes"]
    assert len(report["burst_groups"]) >= 2
    assert any(e["kind"] == "topology_burst"
               for e in report["faults_injected"])


# ------------------------------------- new modalities score end to end
def test_categorical_burst_detected_at_miniature_scale():
    """The categorical modality's reason to exist, scored through the
    real replay pipeline at the 32-col tier-1 geometry: a novel-class
    burst drives the likelihood out of the steady band."""
    from rtap_tpu.data.synthetic import (
        SyntheticStreamConfig,
        generate_categorical_stream,
    )
    from rtap_tpu.eval.workload_eval import tiny_eval_configs
    from rtap_tpu.service.loop import replay_streams

    cat_cfg, _tiny, _comp = tiny_eval_configs()
    scfg = SyntheticStreamConfig(length=260, cadence_s=1.0, n_anomalies=1,
                                 inject_after_frac=0.5)
    # 2 steady classes: iid class draws are irreducibly surprising to a
    # sequence learner, so the 32-col miniature needs a low-entropy
    # steady mix to show clean contrast (the full-scale eval artifact
    # covers the 6-class default through the likelihood layer)
    streams = [generate_categorical_stream(f"ev{i}.class", scfg, seed=5,
                                           n_classes=2)
               for i in range(2)]
    res = replay_streams(streams, cat_cfg, backend="cpu", chunk_ticks=64)
    ll = res.log_likelihood
    for si, s in enumerate(streams):
        (w_lo, w_hi), = s.windows
        in_w = (res.timestamps >= w_lo) & (res.timestamps <= w_hi)
        assert ll[in_w, si].max() > ll[~in_w, si].max() + 0.01, \
            f"stream {si}: burst not separable from steady state"


def test_composite_preset_serves_multifield_records():
    """The composite twin runs through the real replay path (oracle
    backend) on {value, delta, event-class} rows without error and
    produces finite scores."""
    from rtap_tpu.data.synthetic import (
        LabeledStream,
        SyntheticStreamConfig,
        generate_stream,
    )
    from rtap_tpu.eval.workload_eval import tiny_eval_configs
    from rtap_tpu.service.loop import replay_streams

    _cat, _tiny, comp_cfg = tiny_eval_configs()
    scfg = SyntheticStreamConfig(length=120, n_anomalies=0)
    base = generate_stream("web-00.cpu", scfg, seed=1)
    rows = np.stack([base.values, base.values,
                     np.zeros_like(base.values)], axis=1)
    s = LabeledStream(base.stream_id, base.timestamps, rows, [], [])
    res = replay_streams([s], comp_cfg, backend="cpu", chunk_ticks=40)
    assert np.isfinite(res.log_likelihood).all()
    assert res.log_likelihood.shape[0] == 120


# ------------------------------------------------- GET /incidents
def test_obs_incidents_route():
    from rtap_tpu.correlate import IncidentCorrelator, TopologyMap
    from rtap_tpu.obs.expo import ExpositionServer
    from rtap_tpu.obs.metrics import TelemetryRegistry

    co = IncidentCorrelator(TopologyMap.infer(), window_s=5, min_streams=2,
                            sink=lambda _r: None,
                            registry=TelemetryRegistry())
    co.observe_alert("a1", "web-00.cpu", 100)
    co.observe_alert("a2", "web-01.cpu", 101)
    for t in range(102, 110):
        co.on_tick(t)
    srv = ExpositionServer(registry=TelemetryRegistry(),
                           correlator=co).start()
    try:
        host, port = srv.address
        body = urllib.request.urlopen(
            f"http://{host}:{port}/incidents", timeout=10).read()
        snap = json.loads(body)
        assert snap["incidents_emitted"] == 1
        assert len(snap["incidents"]) == 1
        assert snap["incidents"][0]["nodes"] == ["web-00", "web-01"]
        assert snap["topology"]["inferring"] is True
        # without a correlator the route 404s (feature off = no surface)
        bare = ExpositionServer(registry=TelemetryRegistry()).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://{bare.address[0]}:{bare.address[1]}/incidents",
                    timeout=10)
            assert ei.value.code == 404
        finally:
            bare.close()
    finally:
        srv.close()
