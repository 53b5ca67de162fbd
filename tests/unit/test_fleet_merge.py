"""Fleet merge core + push protocol (ISSUE 19).

Coverage pins the tentpole's merge semantics and wire discipline:

- QuantileSketch.merge: fuzz — the MERGED sketch's quantiles track
  ``numpy.percentile`` over the POOLED samples (the whole point: fleet
  p99 is the p99 of the pooled observations, never max-of-member-p99s),
  plus merge-of-empty, disjoint bucket geometry (ValueError), and
  window-roll state carried losslessly through state()/from_state().
- merge_metrics: counters sum per (name, labels); gauges gain a
  ``member`` label instead of a dishonest sum.
- merge_slo: pooled window counts + merged-sketch observed quantile;
  window-length conflicts surfaced, not pooled.
- FleetWalker: torn tails wait, CRC corruption resyncs past the bad
  record, well-framed unknown in-band types are skipped whole
  (version skew), out-of-band types are garbage.
- set_build_info: constant-1 identity gauge; the config hash is stable
  per config and moves when the config does.
"""

import json

import numpy as np
import pytest

from rtap_tpu.fleet import (
    FLEET_HELLO,
    FLEET_SNAP,
    FleetWalker,
    merge_metrics,
    merge_sketches,
    merge_slo,
    pack_fleet,
    unpack_payload,
)
from rtap_tpu.obs.health import config_digest, set_build_info
from rtap_tpu.obs.latency import QuantileSketch
from rtap_tpu.obs.metrics import TelemetryRegistry

pytestmark = pytest.mark.quick


# ------------------------------------------------------- sketch merge --
@pytest.mark.parametrize("members", [2, 5])
@pytest.mark.parametrize("dist", ["uniform", "lognormal", "skewed_split"])
def test_merged_sketch_quantiles_fuzz_vs_pooled_numpy(members, dist):
    """Split one pooled sample set across member sketches, merge, and
    pin the merged quantiles against numpy.percentile of the POOL —
    within one bucket ratio, exactly like a single sketch over the same
    data (losslessness means the split is invisible)."""
    rng = np.random.default_rng(members * 7 + hash(dist) % 2**16)
    n = 20_000
    if dist == "uniform":
        vals = rng.uniform(1e-3, 5.0, n)
    elif dist == "lognormal":
        vals = rng.lognormal(-2.0, 1.2, n)
    else:
        # the failover shape: one member fast, the others slow — a
        # max-of-p99s "merge" would be grossly wrong here
        vals = np.concatenate([rng.normal(0.005, 0.001, n // 4),
                               rng.normal(1.0, 0.2, 3 * n // 4)])
    vals = np.clip(vals, 1e-4, 99.0)
    parts = np.array_split(rng.permutation(vals), members)
    states = []
    for part in parts:
        sk = QuantileSketch()
        sk.observe_many(part)
        states.append(json.loads(json.dumps(sk.state())))  # wire form
    merged = merge_sketches(states)
    assert merged is not None
    single = QuantileSketch()
    single.observe_many(vals)
    ratio = 10 ** (1 / 20)
    for q in (0.5, 0.9, 0.99):
        exact = float(np.percentile(vals, q * 100))
        est = merged.quantile(q, "total")
        assert est is not None
        assert exact / ratio <= est <= exact * ratio, (
            f"{dist}/{members}m p{q * 100}: pooled {exact}, merged {est}")
        # merged == single-sketch-over-pool, bucket for bucket
        assert est == single.quantile(q, "total")
    st = merged.state()
    assert int(np.sum(st["total"])) == len(vals)
    assert st["max"] == pytest.approx(float(vals.max()))
    assert st["sum"] == pytest.approx(float(vals.sum()))


def test_merge_sketches_empty_and_zero_count():
    assert merge_sketches([]) is None
    empty = QuantileSketch().state()
    loaded = QuantileSketch()
    loaded.observe_many([0.01, 0.02, 0.03])
    merged = merge_sketches([empty, loaded.state()])
    assert merged.count("total") == 3
    assert merged.quantile(0.5, "total") == loaded.quantile(0.5, "total")


def test_merge_rejects_disjoint_bucket_geometry():
    a = QuantileSketch(per_decade=20)
    b = QuantileSketch(per_decade=10)
    with pytest.raises(ValueError, match="bucket edges"):
        a.merge(b)
    with pytest.raises(ValueError, match="bucket edges"):
        merge_sketches([a.state(), b.state()])


def test_from_state_rejects_wrong_count_length():
    st = QuantileSketch().state()
    st["cur"] = st["cur"][:-2]
    with pytest.raises(ValueError, match="wrong length"):
        QuantileSketch.from_state(st)


def test_window_roll_survives_state_roundtrip_and_merge():
    """cur/prev window split is carried losslessly: a member that rolled
    its window mid-push must merge with the same one-to-two-window
    coverage a local sketch would report."""
    sk = QuantileSketch()
    sk.observe_many([0.010] * 50)
    sk.roll()
    sk.observe_many([1.0] * 50)
    rt = QuantileSketch.from_state(json.loads(json.dumps(sk.state())))
    assert rt.rolls == sk.rolls == 1
    for scope in ("window", "total"):
        assert rt.count(scope) == sk.count(scope)
        assert rt.quantile(0.5, scope) == sk.quantile(0.5, scope)
    other = QuantileSketch()
    other.observe_many([0.10] * 100)
    merged = merge_sketches([sk.state(), other.state()])
    # window scope = cur+prev of BOTH members (100 + 100 observations)
    assert merged.count("window") == 200
    assert merged.count("total") == 200


# ------------------------------------------------------- metrics merge --
def _snap(rows):
    return {"metrics": {"metrics": rows}}


def test_merge_metrics_sums_counters_and_labels_gauges():
    snaps = {
        "A": _snap([
            {"name": "rtap_obs_ticks_total", "type": "counter",
             "value": 10},
            {"name": "rtap_obs_x_total", "type": "counter",
             "labels": {"k": "1"}, "value": 3},
            {"name": "rtap_obs_run_epoch", "type": "gauge", "value": 2},
        ]),
        "B": _snap([
            {"name": "rtap_obs_ticks_total", "type": "counter",
             "value": 32},
            {"name": "rtap_obs_x_total", "type": "counter",
             "labels": {"k": "2"}, "value": 5},
            {"name": "rtap_obs_run_epoch", "type": "gauge", "value": 4},
        ]),
    }
    out = merge_metrics(snaps)
    by_key = {(c["name"], tuple(sorted((c.get("labels") or {}).items()))):
              c for c in out["counters"]}
    assert by_key[("rtap_obs_ticks_total", ())]["value"] == 42
    assert by_key[("rtap_obs_ticks_total", ())]["members"] == 2
    # label sets are separate fleet totals, never pooled across labels
    assert by_key[("rtap_obs_x_total", (("k", "1"),))]["value"] == 3
    assert by_key[("rtap_obs_x_total", (("k", "2"),))]["value"] == 5
    gauges = {(g["name"], g["labels"]["member"]): g["value"]
              for g in out["gauges"]}
    assert gauges[("rtap_obs_run_epoch", "A")] == 2
    assert gauges[("rtap_obs_run_epoch", "B")] == 4


# ----------------------------------------------------------- slo merge --
def _slo_snap(bad, total, sketch_vals, fast_w=60, slow_w=600):
    sk = QuantileSketch()
    sk.observe_many(sketch_vals)
    return {
        "slo": [{"stage": "tick", "target_s": 0.05, "quantile": 0.99,
                 "fast_window_ticks": fast_w, "slow_window_ticks": slow_w,
                 "fast_bad": bad, "fast_total": total,
                 "slow_bad": bad, "slow_total": total,
                 "cum_bad": bad, "cum_total": total, "burn_events": 0}],
        "latency": {"sketches": {"tick": sk.state()}},
    }


def test_merge_slo_pools_counts_and_uses_merged_sketch():
    rng = np.random.default_rng(3)
    fast = rng.uniform(0.001, 0.01, 500)   # member A: comfortably in SLO
    slow = rng.uniform(0.2, 0.4, 500)      # member B: all bad
    snaps = {"A": _slo_snap(0, 500, fast), "B": _slo_snap(500, 500, slow)}
    out = merge_slo(snaps)
    (v,) = out["slos"]
    assert v["samples"] == 1000 and v["bad"] == 500
    assert v["met"] is False and out["met"] is False
    assert sorted(v["members"]) == ["A", "B"]
    # the merged-sketch p99 lands in B's slow mode — and equals the
    # pooled percentile within a bucket ratio (not max of member p99s,
    # which this case cannot distinguish; losslessness is pinned above)
    pooled = float(np.percentile(np.concatenate([fast, slow]), 99))
    ratio = 10 ** (1 / 20)
    assert pooled / ratio <= v["observed_quantile_s"] <= pooled * ratio


def test_merge_slo_surfaces_window_conflicts():
    snaps = {"A": _slo_snap(0, 100, [0.01] * 10),
             "B": _slo_snap(0, 100, [0.01] * 10, fast_w=120)}
    out = merge_slo(snaps)
    (v,) = out["slos"]
    assert v["samples"] == 100  # the conflicting member is NOT pooled
    assert out["window_conflicts"][0]["member"] == "B"


# ------------------------------------------------------------ protocol --
def test_walker_roundtrip_torn_tail_and_resync():
    frames = (pack_fleet(FLEET_HELLO, {"member": "A"})
              + pack_fleet(FLEET_SNAP, {"member": "A", "seq": 1}))
    w = FleetWalker()
    # torn tail: first half yields only complete records, rest completes
    cut = len(frames) - 7
    got = w.feed(frames[:cut])
    got += w.feed(frames[cut:])
    assert [t for t, _ in got] == [FLEET_HELLO, FLEET_SNAP]
    assert unpack_payload(got[1][1])["seq"] == 1
    assert w.garbage_bytes == 0 and w.bad_crc == 0

    # CRC corruption: the bad record is garbage, the next one recovers
    bad = bytearray(pack_fleet(FLEET_SNAP, {"member": "A", "seq": 2}))
    bad[12] ^= 0xFF
    w2 = FleetWalker()
    got = w2.feed(bytes(bad) + pack_fleet(FLEET_SNAP, {"seq": 3}))
    assert [unpack_payload(p)["seq"] for _, p in got] == [3]
    assert w2.bad_crc == 1 and w2.garbage_bytes > 0

    # leading garbage before the first magic
    w3 = FleetWalker()
    got = w3.feed(b"NOISE" + pack_fleet(FLEET_SNAP, {"seq": 4}))
    assert [unpack_payload(p)["seq"] for _, p in got] == [4]
    assert w3.garbage_bytes == 5


def test_walker_skips_version_skew_keeps_stream():
    """A well-framed record in the fleet band with an unknown type is
    dropped WHOLE and counted — never desyncs the records around it."""
    future = pack_fleet(40, {"new_field": True})  # in-band, unknown
    stream = (pack_fleet(FLEET_SNAP, {"seq": 1}) + future
              + pack_fleet(FLEET_SNAP, {"seq": 2}))
    w = FleetWalker()
    got = w.feed(stream)
    assert [unpack_payload(p)["seq"] for _, p in got] == [1, 2]
    assert w.skew_skipped == 1 and w.garbage_bytes == 0
    # a FUTURE PAYLOAD VERSION on a known type: framing passes, the
    # payload decode refuses to guess
    newer = json.dumps({"v": 99, "member": "A"}).encode()
    assert unpack_payload(newer) is None
    # out-of-band type (a journal record in the fleet stream) = garbage
    w2 = FleetWalker()
    from rtap_tpu.resilience.journal import _CRC, _HEADER, _MAGIC
    import zlib
    head = _HEADER.pack(_MAGIC, 1, 2)  # journal TICK type
    rogue = head + b"{}" + _CRC.pack(zlib.crc32(head[2:] + b"{}"))
    got = w2.feed(rogue + pack_fleet(FLEET_SNAP, {"seq": 5}))
    assert [unpack_payload(p)["seq"] for _, p in got] == [5]
    assert w2.garbage_bytes > 0 and w2.skew_skipped == 0


def test_pack_fleet_rejects_out_of_band_type():
    with pytest.raises(ValueError, match="fleet band"):
        pack_fleet(1, {})
    with pytest.raises(ValueError, match="fleet band"):
        pack_fleet(48, {})


# ---------------------------------------------------------- build info --
def test_build_info_gauge_and_config_hash():
    reg = TelemetryRegistry()
    h = set_build_info(role="leader", shard=0, run_epoch=3,
                       config={"cols": 2048, "cells": 32}, registry=reg)
    assert h == config_digest({"cols": 2048, "cells": 32})
    # key order must not move the hash; content must
    assert h == config_digest({"cells": 32, "cols": 2048})
    assert h != config_digest({"cols": 4096, "cells": 32})
    rows = [r for r in reg.snapshot()["metrics"]
            if r["name"] == "rtap_obs_build_info"]
    assert len(rows) == 1
    (row,) = rows
    assert row["value"] == 1
    assert row["labels"] == {"role": "leader", "shard": "0",
                             "run_epoch": "3", "config_hash": h}


# ------------------------------------------------------------- budget --
def test_fleet_publisher_overhead_within_one_percent_of_tick_budget():
    """The CI twin of the python -m rtap_tpu.obs.selfbench bar: even at the soak
    push density (two full snapshot builds per tick over a populated
    registry and full sketch windows) the fleet publisher stays host-
    noise, and note_tick — the only fleet op ON the tick path — is one
    guarded int store."""
    from rtap_tpu.obs.selfbench import measure_fleet

    res = measure_fleet(n=300)
    assert res["per_tick_overhead_frac"] <= 0.01, res
