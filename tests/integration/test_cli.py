"""Operator CLI (`python -m rtap_tpu`) end-to-end: each subcommand drives
its real pipeline at a tiny size and emits parseable JSON."""

import json
import os

import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ENV = {**os.environ, "RTAP_FORCE_CPU": "1"}


def run_cli(*args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", "rtap_tpu", *args],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=timeout,
    )


def test_replay_emits_throughput_stats():
    p = run_cli("replay", "--nodes", "2", "--length", "900", "--backend", "cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["streams"] == 6 and out["ticks"] == 900
    assert out["scored"] == 6 * 900


def test_replay_width_scaled_frozen():
    """--columns selects the width-scaled preset and --freeze runs
    inference-only, through the real CLI (the density + read-only levers
    SCALING.md recommends must be reachable by operators)."""
    p = run_cli("replay", "--nodes", "2", "--length", "100",
                "--columns", "32", "--freeze", "--backend", "cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["scored"] == 6 * 100


def test_serve_rejects_columns_on_nab_preset():
    p = run_cli("serve", "--streams", "a", "--preset", "nab", "--columns", "32")
    assert p.returncode == 2
    assert "cluster preset only" in p.stderr


def test_serve_rejects_freeze_with_auto_register():
    """A frozen elastic serve is a footgun: lazily claimed models would
    never learn and score garbage forever — rejected instantly (before
    backend init), like the other flag-consistency gates."""
    p = run_cli("serve", "--streams", "a", "--freeze", "--auto-register")
    assert p.returncode == 2
    assert "can never learn" in p.stderr


def test_serve_streams_file_form(tmp_path):
    """--streams @file: fleets beyond a few thousand ids exceed the kernel
    argv limit (observed at the 16k-stream soak), so the file form is the
    at-scale registration path. Missing file = instant usage error."""
    p = run_cli("serve", "--streams", "@" + str(tmp_path / "absent.txt"))
    assert p.returncode == 2
    assert "cannot read stream-id file" in p.stderr


def test_serve_tcp_scores_pushed_records(tmp_path):
    alerts = tmp_path / "alerts.jsonl"
    # register via the @file form — the at-scale path (argv has a ~128 KB
    # single-argument limit): this pins the happy-path file parsing
    # (strip, skip blanks) through the real serve flow
    ids_file = tmp_path / "ids.txt"
    ids_file.write_text("a\n\nb\n")
    proc = subprocess.Popen(
        [sys.executable, "-m", "rtap_tpu", "serve",
         "--streams", "@" + str(ids_file),
         "--ticks", "5", "--cadence", "0.2", "--backend", "cpu", "--port", "0",
         "--alerts", str(alerts)],
        cwd=REPO, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )

    # the listener line tells us the bound port
    port = None
    deadline = time.time() + 120
    lines = []

    def feed():
        nonlocal port
        for line in proc.stderr:
            lines.append(line)
            if "listening for JSONL records on" in line:
                port = int(line.rsplit(":", 1)[1])
                break
        # keep draining so the child never blocks on a full pipe
        for line in proc.stderr:
            lines.append(line)

    t = threading.Thread(target=feed, daemon=True)
    t.start()
    while port is None and time.time() < deadline and proc.poll() is None:
        time.sleep(0.05)
    assert port, (proc.poll(), "".join(lines)[-2000:])

    stop = threading.Event()

    def produce():
        from rtap_tpu.service.sources import send_jsonl

        k = 0
        while not stop.is_set():
            try:
                send_jsonl(("127.0.0.1", port),
                           [{"id": "a", "value": 40 + k}, {"id": "b", "value": 60 - k}])
            except OSError:
                pass
            k += 1
            time.sleep(0.1)

    pt = threading.Thread(target=produce, daemon=True)
    pt.start()
    out, _ = proc.communicate(timeout=300)
    stop.set()
    assert proc.returncode == 0, "".join(lines)[-2000:]
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["ticks"] == 5 and stats["scored"] == 10
    assert "latency_p50_ms" in stats


def test_serve_rejects_bad_chaos_spec(tmp_path):
    """A malformed --chaos-spec is a usage error caught BEFORE any
    listener or registry exists — no half-started serve to clean up."""
    bad = tmp_path / "chaos.json"
    bad.write_text('{"faults": [{"kind": "meteor_strike", "tick": 0}]}')
    p = run_cli("serve", "--streams", "a", "--backend", "cpu",
                "--chaos-spec", str(bad))
    assert p.returncode == 2
    assert "bad --chaos-spec" in p.stderr


def test_serve_rejects_bad_degrade_params():
    """Invalid --degrade knobs are a usage error (exit 2 + message), not
    a traceback — same contract as every other serve flag."""
    p = run_cli("serve", "--streams", "a", "--backend", "cpu",
                "--degrade", "--degrade-after", "11")
    assert p.returncode == 2
    assert "bad --degrade parameters" in p.stderr


def test_serve_chaos_spec_quarantines_and_survives(tmp_path):
    """serve --chaos-spec end to end: a scripted dispatch exception
    quarantines its group mid-serve; the process exits 0 with the
    quarantine in its stats line and the event on the alert stream."""
    spec = tmp_path / "chaos.json"
    spec.write_text(json.dumps({"seed": 7, "faults": [
        {"kind": "dispatch_exception", "tick": 2, "group": 1},
        {"kind": "source_timeout", "tick": 1},
    ]}))
    alerts = tmp_path / "alerts.jsonl"
    # two single-stream groups; no feeder (the TCP source yields NaN
    # ticks, the documented missing-sample path)
    p = run_cli("serve", "--streams", "a,b", "--group-size", "1",
                "--ticks", "5", "--cadence", "0.05", "--backend", "cpu",
                "--alerts", str(alerts), "--chaos-spec", str(spec))
    assert p.returncode == 0, p.stderr[-2000:]
    assert "chaos spec loaded (2 faults" in p.stderr
    stats = json.loads(p.stdout.strip().splitlines()[-1])
    assert stats["ticks"] == 5
    # group 1 scored ticks 0-1 then quarantined; group 0 never skipped one
    assert stats["scored_by_group"] == [5, 2]
    assert stats["quarantine_log"][0]["group"] == 1
    assert stats["chaos_injected"] == 2
    events = [json.loads(line) for line in alerts.read_text().splitlines()
              if line.startswith('{"event"')]
    assert "group_quarantined" in {e["event"] for e in events}


def test_serve_trace_out_and_postmortem_dir_end_to_end(tmp_path):
    """ISSUE 4 CLI surface: serve --trace-out writes Perfetto-loadable
    Chrome trace JSON on exit, and --postmortem-dir auto-dumps a valid
    bundle when a scripted fault quarantines a group — all through the
    real operator command."""
    spec = tmp_path / "chaos.json"
    spec.write_text(json.dumps({"seed": 7, "faults": [
        {"kind": "dispatch_exception", "tick": 2, "group": 1}]}))
    trace_out = tmp_path / "trace.json"
    pm_dir = tmp_path / "pm"
    p = run_cli("serve", "--streams", "a,b", "--group-size", "1",
                "--ticks", "5", "--cadence", "0.05", "--backend", "cpu",
                "--alerts", str(tmp_path / "alerts.jsonl"),
                "--chaos-spec", str(spec),
                "--trace-out", str(trace_out),
                "--postmortem-dir", str(pm_dir),
                "--alert-attribution")
    assert p.returncode == 0, p.stderr[-2000:]
    stats = json.loads(p.stdout.strip().splitlines()[-1])
    assert stats["postmortem"]["bundles"] >= 1
    # the host timeline landed, schema-valid
    tj = json.loads(trace_out.read_text())
    spans = [e for e in tj["traceEvents"] if e.get("ph") == "X"]
    assert {"tick", "source", "dispatch"} <= {e["name"] for e in spans}
    assert any(e.get("ph") == "i" and e["name"] == "group_quarantined"
               and e["args"]["tick"] == 2 for e in tj["traceEvents"])
    # the bundle validates and names the quarantine
    from rtap_tpu.obs import validate_bundle

    bundles = [d for d in pm_dir.iterdir() if not d.name.startswith(".tmp")]
    assert len(bundles) == stats["postmortem"]["bundles"]
    verdicts = {v["reason"]: v for v in map(validate_bundle, map(str, bundles))}
    assert all(v["ok"] for v in verdicts.values()), verdicts
    q = verdicts["group_quarantined"]  # a miss-burst bundle may ride along
    assert q["tick"] == 2
    q_dir = next(d for d in bundles if "group_quarantined" in d.name)
    # and scripts/postmortem.py renders it with exit 0
    pp = subprocess.run(
        [sys.executable, "scripts/postmortem.py", str(q_dir)],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=120)
    assert pp.returncode == 0, pp.stderr[-2000:]
    assert "group_quarantined" in pp.stdout


def test_nab_command_end_to_end(tmp_path):
    """`python -m rtap_tpu nab` — the SURVEY §6 drop-in drill: run the
    committed NAB-layout stand-in corpus (truncated + width-scaled for CPU
    cost) end to end, scores for all three profiles, report JSON written.
    Pointing --corpus at a real NAB checkout is the identical invocation."""
    out = tmp_path / "nab.json"
    p = run_cli("nab", "--rows", "600", "--columns", "64",
                "--subset", "realAWSCloudwatch",
                "--out", str(out), timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    scores = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(scores) == {"standard", "reward_low_FP", "reward_low_FN"}
    rep = json.loads(out.read_text())
    assert rep["records"] == 600 * 6  # six realAWSCloudwatch files
    assert rep["files"][0].startswith("realAWSCloudwatch/")
    for prof in scores.values():
        assert -200.0 <= prof["score"] <= 100.0


def test_nab_command_missing_corpus_fails_loudly(tmp_path):
    p = run_cli("nab", "--corpus", str(tmp_path / "nowhere"))
    assert p.returncode == 2
    assert "combined_windows.json" in p.stderr


def test_serve_fleet_flag_usage_errors():
    """The --fleet-* gates fire BEFORE backend init (exit 2 + message),
    the same contract as every other serve flag (ISSUE 19)."""
    p = run_cli("serve", "--streams", "a", "--fleet-join", "nocolon")
    assert p.returncode == 2
    assert "bad --fleet-join" in p.stderr
    p = run_cli("serve", "--streams", "a", "--fleet-join", "host:99999")
    assert p.returncode == 2
    assert "bad --fleet-join" in p.stderr
    # the aggregator's merged views ride the obs server: no --obs-port,
    # no /fleet/* routes to serve them on
    p = run_cli("serve", "--streams", "a", "--fleet-listen", "0")
    assert p.returncode == 2
    assert "--obs-port" in p.stderr
    p = run_cli("serve", "--streams", "a", "--fleet-push-interval", "0.5")
    assert p.returncode == 2
    assert "--fleet-join" in p.stderr
    p = run_cli("serve", "--streams", "a",
                "--fleet-join", ":9999", "--fleet-push-interval", "0")
    assert p.returncode == 2
    assert "must be > 0" in p.stderr


def test_serve_node_preset_flag_gates():
    """--fields sizes the node preset only, within what the native parser's
    stack row holds; a multi-field model is served by the TCP JSONL listener
    alone (the HTTP poll and the RB1 batch path hold one scalar an id).
    Usage errors, before the backend comes up."""
    p = run_cli("serve", "--streams", "a", "--fields", "3")
    assert p.returncode == 2 and "--preset" in p.stderr
    p = run_cli("serve", "--streams", "a", "--preset", "node",
                "--fields", "65")
    assert p.returncode == 2 and "1..64" in p.stderr
    for flags in (("--http", "http://127.0.0.1:1/m"), ("--ingest-port", "0")):
        p = run_cli("serve", "--streams", "a", "--preset", "node", *flags)
        assert p.returncode == 2 and "TCP JSONL" in p.stderr


def test_serve_node_preset_scores_vector_records(tmp_path):
    """`serve --preset node --fields 3` builds node_preset(3) and its
    listener takes three values a record — F from the model, never from a
    record: a scalar record is a parse error there, a `null` field a
    missing metric of a record that is still scored."""
    import re

    from rtap_tpu.config import node_preset

    alerts = tmp_path / "alerts.jsonl"
    ck = tmp_path / "ck"
    proc = subprocess.Popen(
        [sys.executable, "-m", "rtap_tpu", "serve", "--streams", "n0,n1",
         "--preset", "node", "--fields", "3", "--ticks", "5",
         "--cadence", "0.2", "--backend", "cpu", "--port", "0",
         "--alerts", str(alerts), "--checkpoint-dir", str(ck),
         "--checkpoint-every", "5"],
        cwd=REPO, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    port, lines = None, []

    def drain():
        nonlocal port
        for line in proc.stderr:
            lines.append(line)
            m = re.search(r"listening for JSONL records on \S+?:(\d+)", line)
            if m:
                port = int(m.group(1))

    threading.Thread(target=drain, daemon=True).start()
    deadline = time.time() + 120
    while port is None and time.time() < deadline and proc.poll() is None:
        time.sleep(0.05)
    assert port, (proc.poll(), "".join(lines)[-2000:])
    assert any('"values": a list of 3' in ln for ln in lines)
    stop = threading.Event()

    def produce():
        from rtap_tpu.service.sources import send_jsonl

        k = 0
        while not stop.is_set():
            try:
                send_jsonl(("127.0.0.1", port), [
                    {"id": "n0", "values": [40 + k, None, 5.5]},
                    {"id": "n1", "values": [60 - k, 30.0, 7.0]},
                    {"id": "n1", "value": 1.0}])  # scalar: a parse error
            except OSError:
                pass
            k += 1
            time.sleep(0.1)

    threading.Thread(target=produce, daemon=True).start()
    out, _ = proc.communicate(timeout=300)
    stop.set()
    assert proc.returncode == 0, "".join(lines)[-2000:]
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["ticks"] == 5 and stats["scored"] == 10
    # the checkpoint's model config is the preset's, nothing overridden
    found = [os.path.join(d, f) for d, _s, fs in os.walk(ck) for f in fs
             if f.endswith(".json")]
    configs = []
    for path in found:
        with open(path) as f:
            meta = json.load(f)
        cfg = meta.get("config") or meta.get("model_config")
        if isinstance(cfg, dict) and "n_fields" in cfg:
            configs.append(cfg)
    assert configs and all(c == node_preset(3).to_dict() for c in configs), \
        found
