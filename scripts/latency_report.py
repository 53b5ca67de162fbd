"""Render a detection-latency report: waterfall, quantiles, SLO verdict.

One renderer for every surface the latency layer exports (ISSUE 11):

- ``--report FILE``   — a serve/soak stats JSON whose ``latency`` /
  ``slo`` blocks (live_loop's ``stats["latency"]``/``stats["slo"]``,
  embedded verbatim by the soak harnesses) become the report body;
- ``--url BASE``      — a live obs server: GET ``BASE/latency`` and
  ``BASE/slo`` (404s tolerated — report what is armed);
- ``--snapshot FILE`` — an obs snapshot JSONL: the registry's
  ``rtap_obs_latency_*`` / ``rtap_obs_slo_*`` gauges, last line wins.

Prints ONE JSON line to stdout (the artifact contract shared with the
benches) and a human-readable waterfall/SLO table to stderr.
``--obs-bench-log FILE`` merges the gate lines of ``python -m
rtap_tpu.obs.selfbench`` into the output's ``obs_bench`` block — how reports/latency_r11.json carries
its overhead evidence next to its quantiles. ``--out FILE`` also writes
the merged report as indented JSON (the committed-artifact form).

Usage:
  python scripts/latency_report.py --report reports/live_soak.json
  python scripts/latency_report.py --url http://127.0.0.1:9100
  python scripts/latency_report.py --snapshot soak.obs.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _from_report(path: str) -> dict:
    with open(path) as f:
        rep = json.load(f)
    out = {"source": os.path.abspath(path)}
    for key in ("latency", "slo", "slo_verdict"):
        if key in rep and rep[key] is not None:
            out["slo" if key == "slo_verdict" else key] = rep[key]
    if "latency" not in out and "slo" not in out:
        raise SystemExit(
            f"{path} carries no latency/slo block — was the run armed "
            "with --latency/--slo?")
    return out


def _from_url(base: str) -> dict:
    import urllib.error
    import urllib.request

    out: dict = {"source": base}
    for route, key in (("/latency", "latency"), ("/slo", "slo")):
        try:
            with urllib.request.urlopen(base.rstrip("/") + route,
                                        timeout=10) as r:
                out[key] = json.loads(r.read())
        except urllib.error.HTTPError as e:
            if e.code != 404:  # 404 = not armed; anything else is real
                raise
    if "latency" not in out and "slo" not in out:
        raise SystemExit(f"{base}: neither /latency nor /slo is armed")
    return out


def _from_snapshot(path: str) -> dict:
    from rtap_tpu.obs import read_last_snapshot, summarize_snapshot

    snap = read_last_snapshot(path)
    if snap is None:
        raise SystemExit(f"no parseable snapshot line in {path}")
    summary = summarize_snapshot(snap)
    # prefixes built by concatenation so the metric-catalog drift gate
    # (which scans string literals) doesn't read them as registrations
    pfx = "rtap_obs_"
    wanted = (pfx + "latency", pfx + "slo", pfx + "last_tick_unixtime")
    picked = {k: v for k, v in summary.items() if k.startswith(wanted)}
    if not picked:
        raise SystemExit(
            f"{path} carries no rtap_obs_latency_*/rtap_obs_slo_* "
            "metrics — was the run armed with --latency/--slo?")
    return {"source": os.path.abspath(path), "registry": picked}


def _fmt_s(v) -> str:
    if v is None:
        return "-"
    v = float(v)
    return f"{v * 1e3:.2f}ms" if v < 1.0 else f"{v:.3f}s"


def render_human(rep: dict) -> list[str]:
    """The stderr triage table (docs/SLO.md triage order: verdict ->
    burn -> waterfall stage)."""
    lines = []
    slo = rep.get("slo")
    if slo:
        lines.append(f"SLO verdict: {'MET' if slo.get('met') else 'MISSED'}")
        for v in slo.get("slos", []):
            # met=None is NO DATA (zero observations) — render it as
            # such, never as a violation (the slo.py verdict contract)
            status = ("n/a" if v["met"] is None
                      else "met" if v["met"] else "MISS")
            lines.append(
                f"  {v['slo']:<22} {status:<4} "
                f"observed {_fmt_s(v.get('observed_quantile_s')):>10} "
                f"bad {v['bad']}/{v['samples']} "
                f"budget_left {v['budget_remaining']:+.2f} "
                f"burns {v['burn_events']}")
    lat = rep.get("latency")
    if lat:
        stages = dict(lat.get("stages") or {})
        det = lat.get("detect")
        if det is not None:
            stages = {**stages, "detect": det}
        lines.append(f"Stage quantiles ({lat.get('ticks', '?')} ticks, "
                     f"{lat.get('detect_samples', 0)} detect samples):")
        for name, sk in stages.items():
            q = sk.get("total", sk) if isinstance(sk, dict) else {}
            lines.append(
                f"  {name:<10} p50 {_fmt_s(q.get('p50')):>10} "
                f"p95 {_fmt_s(q.get('p95')):>10} "
                f"p99 {_fmt_s(q.get('p99')):>10} "
                f"p99.9 {_fmt_s(q.get('p99.9')):>10} "
                f"n={q.get('count', 0)}")
        wf = lat.get("waterfall")
        if wf:
            lines.append(f"Last waterfall (tick {wf.get('tick')}):")
            for k in ("arrival_lag_s", "backfill_hold_s", "ingest_lag_s",
                      "dispatch_s", "collect_s", "emit_s", "tick_s"):
                if wf.get(k) is not None:
                    lines.append(f"  {k:<16} {_fmt_s(wf[k])}")
            for k, v in (wf.get("lags") or {}).items():
                lines.append(f"  lag:{k:<12} {v}")
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--report", help="serve/soak stats JSON with "
                                      "latency/slo blocks")
    src.add_argument("--url", help="live obs server base URL "
                                   "(GET /latency + /slo)")
    src.add_argument("--snapshot", help="obs snapshot JSONL (registry "
                                        "gauges; last line wins)")
    ap.add_argument("--obs-bench-log", default=None,
                    help="python -m rtap_tpu.obs.selfbench output to merge "
                         "(one JSON line per gate) — the overhead "
                         "evidence block")
    ap.add_argument("--out", default=None,
                    help="also write the merged report as indented JSON "
                         "(the committed-artifact form)")
    args = ap.parse_args()

    if args.report:
        rep = _from_report(args.report)
    elif args.url:
        rep = _from_url(args.url)
    else:
        rep = _from_snapshot(args.snapshot)

    if args.obs_bench_log:
        gates = []
        with open(args.obs_bench_log) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    gates.append(json.loads(line))
                except ValueError:
                    continue
        rep["obs_bench"] = {
            "gates": gates,
            "all_pass": bool(gates) and all(
                g.get("pass_1pct_budget") for g in gates),
        }

    for line in render_human(rep):
        print(line, file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2)
    print(json.dumps(rep))
    slo = rep.get("slo")
    return 0 if slo is None or slo.get("met", True) else 4


if __name__ == "__main__":
    sys.exit(main())
