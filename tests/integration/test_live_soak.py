"""Soak-harness orchestration smoke (scripts/live_soak.py) at tiny scale.

The soak script is the round-4 "realistic-G live serving" evidence path
(SURVEY.md §3.3; round-3 verdict weak #7): it launches the REAL
`python -m rtap_tpu serve` child, parses its listener line, attaches an
in-process TCP feeder, and commits a stats artifact. This test runs that
whole orchestration at smoke scale on the CPU platform — it exists because
the feeder's deferred `rtap_tpu` import was broken for script-style
invocation (`python scripts/live_soak.py` puts scripts/, not the repo, at
sys.path[0]) and nothing exercised the script end to end before a
hardware window would have.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_live_soak_smoke(tmp_path):
    out = tmp_path / "soak.json"
    env = {**os.environ, "RTAP_FORCE_CPU": "1"}
    # invoked exactly as hw_session invokes it: script path, repo cwd
    proc = subprocess.run(
        [sys.executable, "scripts/live_soak.py",
         "--streams", "8", "--ticks", "4", "--cadence", "0.5",
         "--backend", "tpu", "--startup-timeout", "240",
         "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    art = json.loads(out.read_text())
    assert art["streams"] == 8
    assert art["forced_cpu"] is True
    # data actually flowed (rc==0 already implies the script's own
    # feeder-shortfall check passed; assert only the recorded facts)
    assert art["feeder_error"] is None
    assert art["ticks"] == 4
    assert "missed_deadlines" in art and "latency_p99_ms" in art
    # serve merges ingest health into its stats line (records_parsed is
    # present whenever the native parser is active; counters must be clean)
    assert art["parse_errors"] == 0 and art["unknown_ids"] == 0
    if art.get("native_active"):
        assert art["records_parsed"] > 0
