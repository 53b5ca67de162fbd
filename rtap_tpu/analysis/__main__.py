"""CLI: ``python -m rtap_tpu.analysis [--json] [--sarif PATH]
[--rules ...] [--no-cache]``.

Exit codes: 0 = zero unsuppressed findings (the gate), 1 = findings or
baseline format errors, 2 = usage error. The human report goes to
stderr; ``--json`` prints exactly one JSON artifact line to stdout (the
soak archival surface — the evals' one-JSON-line stdout
contract), so both can be combined in one invocation. ``--sarif``
writes a SARIF 2.1.0 log to a FILE (never stdout — the one-line
contract stays intact) for CI/editor rendering.

Full runs are served from the per-file content-hash findings cache
(``<root>/.rtap_lint_cache.json``, gitignored): any file edit, add,
delete, docs change, baseline change, or analyzer change re-runs cold;
an untouched tree replays the identical report sub-second. ``--rules``
subsets bypass the cache entirely, ``--no-cache`` forces a cold run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from rtap_tpu.analysis import ALL_RULES
from rtap_tpu.analysis.core import (
    BASELINE_NAME,
    Baseline,
    render_human,
    run_analysis,
    run_analysis_cached,
)


def _default_root() -> str:
    """The repo root: the cwd when it holds rtap_tpu/, else the package's
    grandparent (so the module runs from anywhere inside the checkout)."""
    cwd = os.getcwd()
    if os.path.isdir(os.path.join(cwd, "rtap_tpu")):
        return cwd
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m rtap_tpu.analysis",
        description="rtap-lint: AST-based invariant analysis "
                    "(docs/ANALYSIS.md)")
    ap.add_argument("--root", default=None,
                    help="repo root to analyze (default: auto-detected)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON artifact line on stdout "
                         "(findings, counts, timings)")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: <root>/{BASELINE_NAME})")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run (default: all; "
                         "subsets bypass the findings cache)")
    ap.add_argument("--sarif", default=None, metavar="PATH",
                    help="also write a SARIF 2.1.0 log to PATH (CI/"
                         "editor rendering; stdout keeps the one-line "
                         "--json contract)")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore and do not write the findings cache "
                         "(forces a cold run)")
    ap.add_argument("--cache-path", default=None, metavar="PATH",
                    help="findings cache location (default: "
                         "<root>/.rtap_lint_cache.json)")
    ap.add_argument("--list-passes", action="store_true",
                    help="list rule ids + descriptions and exit")
    ap.add_argument("--update-baseline", action="store_true",
                    help="mechanical baseline maintenance: re-key moved "
                         "symbols (whys preserved verbatim), drop stale "
                         "entries; REFUSES to mint entries for new "
                         "findings (a why-less entry is a gate failure "
                         "by design)")
    args = ap.parse_args(argv)

    if args.list_passes:
        for rid, desc in sorted(ALL_RULES.items()):
            print(f"{rid:18s} {desc}", file=sys.stderr)
        return 0

    root = args.root or _default_root()
    if not os.path.isdir(os.path.join(root, "rtap_tpu")):
        print(f"rtap-lint: {root} does not look like the repo root "
              "(no rtap_tpu/)", file=sys.stderr)
        return 2
    rules = None
    if args.rules:
        rules = {r.strip() for r in args.rules.split(",") if r.strip()}
        unknown = rules - set(ALL_RULES) - {"parse-error"}
        if unknown:
            print(f"rtap-lint: unknown rule(s): {sorted(unknown)} "
                  f"(known: {sorted(ALL_RULES)})", file=sys.stderr)
            return 2
    baseline_path = args.baseline or os.path.join(root, BASELINE_NAME)
    if args.update_baseline:
        from rtap_tpu.analysis.baseline_update import update_baseline

        summary = update_baseline(root, baseline_path=baseline_path)
        for old, new in summary["rekeyed"]:
            print(f"rekeyed: {':'.join(old)} -> {':'.join(new)}",
                  file=sys.stderr)
        for key in summary["dropped"]:
            print(f"dropped stale: {':'.join(key)}", file=sys.stderr)
        for key in summary["unmatched"]:
            print(f"NOT baselined (write the why yourself): "
                  f"{':'.join(key)}", file=sys.stderr)
        for e in summary["format_errors"]:
            print(f"left malformed entry for a human: {e}",
                  file=sys.stderr)
        print(f"--update-baseline: {len(summary['rekeyed'])} rekeyed, "
              f"{len(summary['dropped'])} dropped, "
              f"{len(summary['unmatched'])} refused, "
              f"{'wrote' if summary['wrote'] else 'no change to'} "
              f"{baseline_path}", file=sys.stderr)
        return 1 if summary["unmatched"] or summary["format_errors"] \
            else 0
    if rules is None and not args.no_cache:
        report = run_analysis_cached(root, baseline_path=baseline_path,
                                     cache_path=args.cache_path)
    else:
        report = run_analysis(root, baseline=Baseline.load(baseline_path),
                              rules=rules)
    print(render_human(report), file=sys.stderr)
    if args.json:
        print(json.dumps(report.to_dict()))
    if args.sarif:
        from rtap_tpu.analysis.sarif import to_sarif

        with open(args.sarif, "w", encoding="utf-8") as fh:
            json.dump(to_sarif(report), fh, indent=2)
            fh.write("\n")
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
