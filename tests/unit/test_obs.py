"""rtap_tpu.obs primitives: instrument semantics, exposition formats,
watchdog event detection, and the <= 1%-of-tick overhead bar.

The telemetry registry is the seam every serve-path hot loop emits through
(ISSUE 1 tentpole); these tests pin the parts the loop depends on blind:
Prometheus `le` bucket-edge semantics, snapshot idempotence (a scrape must
not perturb state), lock-free correctness under concurrent writer threads
(the dispatch pool emits), and the self-measured overhead budget.
"""

import json
import threading

import numpy as np
import pytest

from rtap_tpu.obs import (
    TelemetryRegistry,
    TickWatchdog,
    log_buckets,
    render_prometheus,
    summarize_snapshot,
)
from rtap_tpu.obs.selfbench import measure


# ---------------------------------------------------------- instruments ----


def test_counter_inc_and_monotonicity():
    reg = TelemetryRegistry()
    c = reg.counter("t_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    reg = TelemetryRegistry()
    g = reg.gauge("t_gauge")
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value == 4.0


def test_histogram_bucket_edges_le_semantics():
    """Prometheus `le` semantics: v lands in the FIRST bucket with v <= edge;
    values above the top edge land in +Inf."""
    reg = TelemetryRegistry()
    h = reg.histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.100001, 1.0, 10.0, 10.1):
        h.observe(v)
    snap = h.snapshot_value()
    # cumulative counts at each edge
    assert snap["buckets"] == {
        "0.1": 2,        # 0.05, 0.1 (edge value is INCLUDED)
        "1.0": 4,        # + 0.100001, 1.0
        "10.0": 5,       # + 10.0
        "+Inf": 6,       # + 10.1
    }
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(21.350001)
    assert snap["min"] == pytest.approx(0.05)
    assert snap["max"] == pytest.approx(10.1)


def test_histogram_rejects_bad_buckets():
    reg = TelemetryRegistry()
    with pytest.raises(ValueError):
        reg.histogram("bad_seconds", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("bad2_seconds", buckets=(2.0, 1.0))


def test_log_buckets_cover_tick_range():
    edges = log_buckets()
    assert edges[0] == pytest.approx(1e-3)
    assert edges[-1] == pytest.approx(10.0)
    assert all(b > a for a, b in zip(edges, edges[1:]))


def test_registry_get_or_create_and_type_conflict():
    reg = TelemetryRegistry()
    a = reg.counter("x_total", phase="source")
    b = reg.counter("x_total", phase="source")
    assert a is b  # cached per (name, labels): call sites may re-fetch
    c = reg.counter("x_total", phase="emit")
    assert c is not a  # distinct label set = distinct child
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # one name, one type


def test_snapshot_idempotent_and_json_serializable():
    """Two snapshots with no writes in between are identical (scraping must
    not perturb state), and the snapshot round-trips through json."""
    reg = TelemetryRegistry()
    reg.counter("a_total").inc(3)
    reg.gauge("b").set(1.5)
    h = reg.histogram("c_seconds", buckets=(0.5, 5.0))
    h.observe(0.2)
    s1, s2 = reg.snapshot(), reg.snapshot()
    assert s1["metrics"] == s2["metrics"]
    assert json.loads(json.dumps(s1))["metrics"] == s1["metrics"]


def test_concurrent_writers_lose_nothing():
    """8 threads hammering one counter and one histogram: the per-thread
    cell sharding must make every increment and observation land (the
    dispatch pool emits concurrently with the loop thread)."""
    reg = TelemetryRegistry()
    c = reg.counter("cc_total")
    h = reg.histogram("ch_seconds", buckets=(0.5, 5.0))
    n_threads, n_ops = 8, 5000

    def work():
        for _ in range(n_ops):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_ops
    assert h.count == n_threads * n_ops
    assert h.snapshot_value()["buckets"]["0.5"] == n_threads * n_ops


def test_registry_reset_zeroes_but_keeps_instruments():
    reg = TelemetryRegistry()
    c = reg.counter("r_total")
    c.inc(5)
    reg.reset()
    assert c.value == 0
    assert reg.counter("r_total") is c  # cached references stay valid
    c.inc()
    assert c.value == 1


# ----------------------------------------------------------- exposition ----


def test_prometheus_exposition_golden():
    """The exact text a scraper sees: HELP/TYPE headers, label rendering,
    cumulative histogram buckets, _sum/_count. Format drift breaks real
    Prometheus ingestion, so this is a golden comparison, not a grep."""
    reg = TelemetryRegistry()
    reg.counter("g_ticks_total", "ticks completed").inc(7)
    reg.gauge("g_streams", "live streams").set(3)
    h = reg.histogram("g_phase_seconds", "per-phase seconds",
                      buckets=(0.1, 1.0), phase="emit")
    h.observe(0.05)
    h.observe(0.05)
    h.observe(2.0)
    assert render_prometheus(reg) == (
        '# HELP g_phase_seconds per-phase seconds\n'
        '# TYPE g_phase_seconds histogram\n'
        'g_phase_seconds_bucket{phase="emit",le="0.1"} 2\n'
        'g_phase_seconds_bucket{phase="emit",le="1"} 2\n'
        'g_phase_seconds_bucket{phase="emit",le="+Inf"} 3\n'
        'g_phase_seconds_sum{phase="emit"} 2.1\n'
        'g_phase_seconds_count{phase="emit"} 3\n'
        '# HELP g_streams live streams\n'
        '# TYPE g_streams gauge\n'
        'g_streams 3\n'
        '# HELP g_ticks_total ticks completed\n'
        '# TYPE g_ticks_total counter\n'
        'g_ticks_total 7\n'
    )


def test_summarize_snapshot_flattens_for_artifacts():
    reg = TelemetryRegistry()
    reg.counter("s_total", phase="a").inc(2)
    h = reg.histogram("s_seconds", buckets=(1.0,))
    h.observe(0.5)
    h.observe(1.5)
    s = summarize_snapshot(reg.snapshot())
    assert s["s_total{phase=a}"] == 2
    assert s["s_seconds"]["count"] == 2
    assert s["s_seconds"]["mean"] == pytest.approx(1.0)
    assert s["s_seconds"]["max"] == pytest.approx(1.5)


# -------------------------------------------------------------- watchdog ----


def test_watchdog_missed_tick_detection():
    reg = TelemetryRegistry()
    events = []
    wd = TickWatchdog(1.0, registry=reg, event_sink=events.append)
    assert wd.observe_tick(0, 0.5) is False
    assert wd.observe_tick(1, 1.0) is False  # exactly on budget = made it
    assert wd.observe_tick(2, 1.25) is True
    assert reg.counter("rtap_obs_missed_ticks_total").value == 1
    assert events == [{"event": "missed_tick", "tick": 2,
                       "elapsed_s": 1.25, "cadence_s": 1.0}]


def test_watchdog_source_starvation_runs():
    reg = TelemetryRegistry()
    events = []
    wd = TickWatchdog(1.0, registry=reg, event_sink=events.append,
                      starved_after=3)
    nan3 = np.full(3, np.nan, np.float32)
    some = np.array([np.nan, 2.0, np.nan], np.float32)
    for k in range(2):
        wd.observe_source(k, nan3)
    assert events == []  # below the threshold: ordinary missing samples
    wd.observe_source(2, nan3)
    assert events == [{"event": "source_starved", "tick": 2,
                       "consecutive_ticks": 3}]
    wd.observe_source(3, some)  # ANY real value resets the run
    for k in range(4, 7):
        wd.observe_source(k, nan3)
    assert len(events) == 2 and events[1]["consecutive_ticks"] == 3


def test_watchdog_checkpoint_stall():
    reg = TelemetryRegistry()
    events = []
    wd = TickWatchdog(1.0, registry=reg, event_sink=events.append)
    wd.observe_checkpoint(5, 0.3)  # under budget: expected, no event
    wd.observe_checkpoint(9, 2.5)
    assert [e["event"] for e in events] == ["checkpoint_stall"]
    assert reg.counter("rtap_obs_watchdog_events_total",
                       event="checkpoint_stall").value == 1


# --------------------------------------------------------------- budget ----


def test_obs_overhead_within_one_percent_of_tick_budget():
    """Acceptance bar (ISSUE 1): a full tick's instrument traffic costs
    <= 1% of the 1 s cadence budget. Measured, not assumed — the same
    measurement python -m rtap_tpu.obs.selfbench ships. Typical hosts land 3-4 orders
    of magnitude under the bar, so this does not flake on slow CI."""
    res = measure(n=5000)
    assert res["per_tick_overhead_frac"] <= 0.01, res
