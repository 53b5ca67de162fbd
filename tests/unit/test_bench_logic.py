"""The bench's emission machinery (bench.py): one line per run that names
the device it ran on, no line at all when nothing was measured, emit
idempotence, the state-bytes gate, the trend series. (The cached
last-known-good re-emission these tests once pinned is gone — no chip, no
number — ISSUE 21.)"""

import importlib.util
import json

TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def load_bench(tmp_path, monkeypatch):
    """Import bench.py as an isolated module with the trend redirected."""
    spec = importlib.util.spec_from_file_location("bench_under_test", "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the trend series is a committed artifact: every test writes to its
    # own sandbox (a _finish() with a result appends a round)
    mod.TREND_PATH = str(tmp_path / "trend_rung.json")
    return mod


def test_emit_prefers_fresh_result(tmp_path, monkeypatch, capsys):
    """A measured result is the only thing ever emitted: exit code 0, the
    value as measured, the device it ran on. With nothing measured, nothing
    is printed and the exit code is 1 — no chip, no number."""
    import pytest

    b = load_bench(tmp_path, monkeypatch)
    assert b.emit(None) is None
    with pytest.raises(SystemExit) as e:
        b._finish(None)
    assert e.value.code == 1
    assert capsys.readouterr().out == ""
    assert b.emit({"value": 42.0, **TPU}) == 0  # fresh result -> exit code 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 42.0 and "cached" not in out
    assert {k: out[k] for k in TPU} == TPU


def test_emit_is_idempotent(tmp_path, monkeypatch, capsys):
    b = load_bench(tmp_path, monkeypatch)
    assert b.emit({"value": 1.0}) == 0
    assert b.emit({"value": 2.0}) == 0  # reports success, prints nothing new
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 1 and json.loads(lines[0])["value"] == 1.0




def test_state_bytes_gate_matches_derivation(tmp_path, monkeypatch, capsys):
    """The honest per-stream figure (real arrays) and the scaling-math static
    derivation must agree on the cluster preset — the gate that keeps
    SCALING.md's capacity table and the actual layout from drifting apart
    (ISSUE 18)."""
    from rtap_tpu.analysis.scalingmath import derived_stream_bytes

    b = load_bench(tmp_path, monkeypatch)
    measured = b.state_bytes_gate()
    assert measured == b._STATE_BYTES == derived_stream_bytes(".", 16)
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert line["state_bytes_gate"] == "pass"
    assert line["state_bytes_per_stream"] == measured
    # the figure rides the emitted result line
    assert b.emit({"value": 42.0}) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["state_bytes_per_stream"] == measured


def test_state_bytes_gate_fails_on_drift(tmp_path, monkeypatch, capsys):
    import pytest

    import rtap_tpu.analysis.scalingmath as sm

    b = load_bench(tmp_path, monkeypatch)
    monkeypatch.setattr(sm, "derived_stream_bytes", lambda root, bits: 1)
    with pytest.raises(SystemExit) as exc:
        b.state_bytes_gate()
    assert exc.value.code == 1
    line = json.loads(capsys.readouterr().err.strip().splitlines()[-2])
    assert line["state_bytes_gate"] == "FAIL"


def test_oom_dominance_skip_logic():
    """The ladder-skip predicate: only configs dominating the observed OOM
    point in BOTH dims are skipped."""
    oom_at = (2048, 64)
    skipped = [
        (g, t) for g, t in [(4096, 64), (2048, 128), (1024, 64), (4096, 32), (2048, 64)]
        if g >= oom_at[0] and t >= oom_at[1]
    ]
    assert skipped == [(4096, 64), (2048, 128), (2048, 64)]




def test_emit_carries_full_rate_alongside_cadence_headline(tmp_path, monkeypatch, capsys):
    """A cadence rung wins the ladder max, so the full-rate default rung's
    number must ride the line as full_rate_value — otherwise a default-
    config regression hides behind an unchanged cadence headline."""
    b = load_bench(tmp_path, monkeypatch)
    b._BEST_FULL = {"value": 32893.3, "G": 256, "T": 256}
    assert b.emit({"value": 120345.6, "modes": "flat/matmul/dense/learn_every=8"}) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 120345.6
    assert out["full_rate_value"] == 32893.3
    assert out["modes"].endswith("learn_every=8")



def test_append_trend_appends_and_preserves_protocol_study(tmp_path, monkeypatch):
    """The full-rate trend rides reports/trend_rung.json as a first-class
    series: every fresh bench appends {round, full_rate, headline} under
    "rounds" WITHOUT clobbering the protocol-study keys trend_rung.py
    owns (ISSUE 3 satellite)."""
    monkeypatch.setenv("BENCH_ROUND", "6")
    b = load_bench(tmp_path, monkeypatch)
    b.TREND_PATH = str(tmp_path / "trend_rung.json")
    (tmp_path / "trend_rung.json").write_text(json.dumps(
        {"novel_feed_metrics_per_s": 32904.0, "config": "x"}))
    b._BEST_FULL = {"value": 33100.4}
    b._append_trend({"value": 86000.2, "modes": "flat/matmul/dense/learn_every=4", **TPU})
    data = json.loads((tmp_path / "trend_rung.json").read_text())
    assert data["novel_feed_metrics_per_s"] == 32904.0  # study keys intact
    assert len(data["rounds"]) == 1
    entry = data["rounds"][0]
    assert entry["round"] == "6"
    assert entry["headline"] == 86000.2
    assert entry["full_rate"] == 33100.4
    # second fresh run appends, never rewrites history
    b._append_trend({"value": 90000.0, "modes": "m", **TPU})
    data = json.loads((tmp_path / "trend_rung.json").read_text())
    assert len(data["rounds"]) == 2


def test_append_trend_records_full_rate_hole(tmp_path, monkeypatch):
    """Every default-config rung failing must show as full_rate: null in
    the series — a hole in the trend, not a silently skipped round."""
    monkeypatch.delenv("BENCH_ROUND", raising=False)
    b = load_bench(tmp_path, monkeypatch)
    b.TREND_PATH = str(tmp_path / "trend_rung.json")
    assert b._BEST_FULL is None
    b._append_trend({"value": 50.0, "modes": "m", **TPU})
    data = json.loads((tmp_path / "trend_rung.json").read_text())
    assert data["rounds"][0]["full_rate"] is None


def test_append_trend_cpu_drive_guard(tmp_path, monkeypatch):
    """A result that did not run on a TPU (an explicit-CPU drive) must never
    touch the committed series — unless $BENCH_TREND_PATH points the drive
    at a file of its own."""
    monkeypatch.delenv("BENCH_TREND_PATH", raising=False)
    b = load_bench(tmp_path, monkeypatch)
    b.TREND_PATH = str(tmp_path / "trend_rung.json")
    b._append_trend({"value": 1.0, "platform": "cpu"})
    b._append_trend({"value": 1.0})  # an unlabeled result is not a TPU one
    assert not (tmp_path / "trend_rung.json").exists()
    monkeypatch.setenv("BENCH_TREND_PATH", b.TREND_PATH)
    b._append_trend({"value": 1.0, "platform": "cpu"})
    entry = json.loads((tmp_path / "trend_rung.json").read_text())["rounds"][0]
    assert entry["platform"] == "cpu"  # and the series says what it was


def test_append_trend_survives_corrupt_artifact(tmp_path, monkeypatch):
    """_append_trend runs inside _finish (including the signal handler):
    a mangled trend artifact must degrade to a fresh series, and a
    non-JSON one must not raise through the emission path."""
    b = load_bench(tmp_path, monkeypatch)
    b.TREND_PATH = str(tmp_path / "trend_rung.json")
    (tmp_path / "trend_rung.json").write_text("{not json")
    b._append_trend({"value": 1.0, **TPU})  # must not raise
    (tmp_path / "trend_rung.json").write_text("[1, 2]")  # wrong shape
    b._append_trend({"value": 2.0, **TPU})
    data = json.loads((tmp_path / "trend_rung.json").read_text())
    assert [e["headline"] for e in data["rounds"]] == [2.0]


def test_infer_round_from_committed_artifacts(tmp_path, monkeypatch):
    """Unattended bench runs label trend entries one past the newest
    BENCH_rNN.json beside bench.py instead of appending null rounds (the
    driver's r01..r05 records were removed by ISSUE 21: none beside the
    real bench.py means no label)."""
    monkeypatch.delenv("BENCH_ROUND", raising=False)
    b = load_bench(tmp_path, monkeypatch)
    assert b._infer_round() is None
    for n in (1, 5, 3):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text("{}")
    monkeypatch.setattr(b, "__file__", str(tmp_path / "bench.py"))
    assert b._infer_round() == "r06"
    monkeypatch.setenv("BENCH_ROUND", "override")
    assert b._infer_round() == "override"
