"""ISSUE 4 acceptance: a chaos-injected group quarantine during serve
auto-dumps a postmortem bundle whose trace file is valid Chrome
trace-event JSON containing phase spans, per-group child spans, and the
group_quarantined instant at the correct tick — and /trace?last=N over
the obs HTTP server returns the same schema live."""

import json
import urllib.request

import numpy as np
import pytest

from rtap_tpu.config import cluster_preset
from rtap_tpu.obs import (
    ExpositionServer,
    FlightRecorder,
    TraceRecorder,
    get_registry,
    summarize_snapshot,
    validate_bundle,
)
from rtap_tpu.resilience import ChaosEngine, ChaosSpec, Fault
from rtap_tpu.service.loop import live_loop
from rtap_tpu.service.registry import StreamGroupRegistry

G_TOTAL = 6
GROUP_SIZE = 2  # 3 groups: quarantine the middle one
N_TICKS = 12
Q_TICK = 5


def _registry():
    reg = StreamGroupRegistry(cluster_preset(), group_size=GROUP_SIZE,
                              backend="tpu")
    for i in range(G_TOTAL):
        reg.add_stream(f"s{i}")
    reg.finalize()
    return reg


def _feed(k):
    rng = np.random.Generator(np.random.Philox(key=(91, k)))
    return (30 + 5 * rng.random(G_TOTAL)).astype(np.float32), \
        1_700_000_000 + k


def _spans(events):
    return [e for e in events if e.get("ph") == "X"]


def _check_timeline(events):
    """The schema contract shared by the bundle's trace.json and the live
    /trace route: phase spans on the loop track, per-group child spans on
    group tracks, the quarantine instant at its tick."""
    spans = _spans(events)
    names = {e["name"] for e in spans}
    # phase spans (checkpoint/membership only fire when they do work)
    assert {"tick", "source", "dispatch", "collect", "emit"} <= names
    # every span carries its tick correlation id
    assert all(isinstance(e["args"]["tick"], int) for e in spans)
    # per-group child spans land on per-group tracks (tid = group + 1)
    for gi in (0, 2):  # healthy groups dispatched every tick
        child = [e for e in spans
                 if e["args"].get("group") == gi and e["name"] == "dispatch"]
        assert child, f"no per-group dispatch child spans for group {gi}"
        assert all(e["tid"] == gi + 1 for e in child)
    # the quarantine instant, at the tick the fault was injected
    q = [e for e in events
         if e.get("ph") == "i" and e["name"] == "group_quarantined"]
    assert len(q) == 1
    assert q[0]["args"]["tick"] == Q_TICK and q[0]["args"]["group"] == 1


@pytest.mark.quick
def test_chaos_quarantine_autodumps_valid_bundle_and_trace_route(tmp_path):
    before = summarize_snapshot(get_registry().snapshot())
    trace = TraceRecorder(capacity=16384)
    # miss_burst above N_TICKS: the compiling CPU backend misses every
    # sub-ms deadline, and this test wants exactly the quarantine bundle
    flight = FlightRecorder(trace=trace, n_ticks=64,
                            out_dir=str(tmp_path / "pm"),
                            miss_burst=N_TICKS + 1,
                            info={"test": "postmortem_serve"})
    reg = _registry()
    stats = live_loop(
        _feed, reg, n_ticks=N_TICKS, cadence_s=0.01,
        alert_path=str(tmp_path / "alerts.jsonl"),
        chaos=ChaosEngine(ChaosSpec(faults=[
            Fault(kind="dispatch_exception", tick=Q_TICK, group=1)])),
        trace=trace, flight=flight)
    assert stats["ticks"] == N_TICKS
    assert stats["quarantine_log"][0]["tick"] == Q_TICK

    # ---- the bundle auto-dumped, atomically, and validates
    assert stats["postmortem"]["bundles"] == 1
    bundles = [d for d in (tmp_path / "pm").iterdir()
               if not d.name.startswith(".tmp")]
    assert len(bundles) == 1
    assert "group_quarantined" in bundles[0].name
    v = validate_bundle(str(bundles[0]))
    assert v["ok"], v
    assert v["reason"] == "group_quarantined" and v["tick"] == Q_TICK
    assert v["spans"] > 0 and v["events"] > 0

    # ---- the bundle's trace is a loadable timeline with the full schema
    tj = json.load(open(bundles[0] / "trace.json"))
    _check_timeline(tj["traceEvents"])
    # the quarantine event line is in the bundle's ledger too
    ledger = [json.loads(l) for l in
              (bundles[0] / "events.jsonl").read_text().splitlines()]
    assert any(e["event"] == "group_quarantined" and e["tick"] == Q_TICK
               for e in ledger)
    summary = json.load(open(bundles[0] / "summary.json"))
    assert summary["ticks"]["count"] > 0
    assert summary["info"]["test"] == "postmortem_serve"

    # ---- /trace?last=N over the obs HTTP server: same schema, live
    with ExpositionServer(trace=trace, flight=flight) as srv:
        host, port = srv.address
        body = urllib.request.urlopen(
            f"http://{host}:{port}/trace?last={N_TICKS}",
            timeout=10).read()
        http_tj = json.loads(body)
        _check_timeline(http_tj["traceEvents"])
        # windowing works: last=1 keeps only the final tick's records
        small = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/trace?last=1", timeout=10).read())
        ticks = {e["args"]["tick"] for e in _spans(small["traceEvents"])}
        # (unticked spans — a garbage collection before the loop's first
        # record, an AOT warm-up program — are kept whatever the window)
        assert ticks - {-1} == {N_TICKS - 1}
        # on-demand postmortem over HTTP (fresh reason, not throttled)
        pm = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/postmortem", timeout=10).read())
        assert pm["bundle"] is not None
        assert validate_bundle(pm["bundle"])["ok"]

    # ---- the new metrics moved
    after = summarize_snapshot(get_registry().snapshot())
    assert after.get(
        "rtap_obs_postmortem_bundles_total{reason=group_quarantined}", 0) \
        - before.get(
            "rtap_obs_postmortem_bundles_total{reason=group_quarantined}",
            0) == 1
    assert after["rtap_obs_trace_records"] > 0


@pytest.mark.quick
@pytest.mark.quick
def test_live_multivariate_alert_carries_top_fields(tmp_path):
    """Satellite: --alert-attribution end to end on the real loop — a
    known per-field spike in a multivariate serve names that field on
    the alert line."""
    from rtap_tpu.config import node_preset
    from rtap_tpu.service.attribution import AlertAttributor

    cfg = node_preset(3)
    reg = StreamGroupRegistry(cfg, group_size=2, backend="tpu",
                              threshold=-1e9, debounce=1)
    for i in range(2):
        reg.add_stream(f"n{i}")
    reg.finalize()

    def feed(k):
        v = np.full((2, 3), 20.0, np.float32)
        if k >= 3:
            v[0, 2] += 300.0  # net on n0 spikes from tick 3 on
        return v, 1_700_000_000 + k

    stats = live_loop(feed, reg, n_ticks=5, cadence_s=0.01,
                      alert_path=str(tmp_path / "alerts.jsonl"),
                      attributor=AlertAttributor(cfg))
    assert stats["alerts"] > 0
    lines = [json.loads(l) for l in
             (tmp_path / "alerts.jsonl").read_text().splitlines()
             if not l.startswith('{"event"')]
    spiked = [l for l in lines if l["stream"] == "n0" and l["ts"] ==
              1_700_000_003]
    assert spiked and spiked[0]["top_fields"][0]["field"] == 2
