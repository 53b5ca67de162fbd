#!/usr/bin/env bash
# Static gates for the WHOLE package + scripts (tier-1 rides this via
# tests/unit/test_static_checks.py):
#
#  1. compileall — every rtap_tpu module AND every scripts/ entry point
#     must at least parse/compile; an import-time SyntaxError must fail
#     CI even if no test imports the file.
#  2. rtap-lint (python -m rtap_tpu.analysis) — the AST invariant
#     analyzer (ISSUEs 12+13+14+15, docs/ANALYSIS.md): twenty passes —
#     the print gate and MUST_BE_STRICT coverage pin, the race, purity,
#     exception-discipline, and flag↔docs passes, the whole-program v2
#     passes (lock-order deadlock cycles, cross-object sharing, replay
#     determinism, resource lifecycle), the device-kernel v3 family
#     (twin-parity, trace-safety, donate-read, static-hash/jit-churn,
#     dtype-domain, wire-contract), and the mesh-readiness v4 family
#     (partition-contract, device-scope, collective-discipline,
#     shard-resource, scaling-math — the ROADMAP-1 rails). Exit 0 iff
#     zero unsuppressed findings against the committed
#     analysis_baseline.json. Untouched-tree reruns are served from the
#     pass-partitioned content-hash findings cache (finding-identical
#     by test).
#
# This script is deliberately a thin wrapper: the checking logic has ONE
# home (rtap_tpu/analysis/), testable as a library, with a --json
# artifact surface for soaks (`python -m rtap_tpu.analysis --json`).
set -euo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q rtap_tpu scripts

python -m rtap_tpu.analysis

echo "check_static: OK"
