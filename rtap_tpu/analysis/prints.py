"""The print gate + strict-coverage pin, ported from check_static.sh.

Rule ``print-strict`` — NO ``print()`` at all in the serve stack
(``service/``, ``obs/``, ``resilience/``, ``ingest/``, ``correlate/``):
telemetry and diagnostics go through rtap_tpu.obs (registry
instruments, watchdog events, snapshots) or logging, never ad-hoc
stdout/stderr lines a harness would have to scrape back out of logs.

Rule ``print-bare`` — everywhere else in ``rtap_tpu/`` and ``scripts/``,
a ``print()`` must either target an explicit stream
(``file=...`` — stderr diagnostics) or be the sanctioned one-JSON-line
stdout emission (a single ``json.dumps(...)``/``.to_json()`` argument —
the bench/eval artifact contract). AST-based: a line grep cannot see a
multi-line call.

Rule ``strict-coverage`` — the MUST_BE_STRICT pin (ISSUE 11): the
serve-path instrumentation modules must exist AND sit under a strict
directory; a rename/move that silently dropped them out of no-print
coverage would let stdout lines creep back into the hot path. Extend
the list with every new serve-path module.

These rules are gate-critical plumbing, so inline suppressions are NOT
honored for them — the canary tests (tests/unit/test_static_checks.py)
guard the guard.
"""

from __future__ import annotations

import ast

from rtap_tpu.analysis.core import AnalysisContext, Finding

PASS_NAME = "prints"
#: cross-file inputs -> all-or-nothing in the findings cache
PARTITION = "program"
RULES = {
    "print-strict": "print() in the serve stack (telemetry goes through "
                    "rtap_tpu.obs or logging)",
    "print-bare": "bare print() outside the serve stack (route to "
                  "stderr via file= or emit a JSON artifact line)",
    "strict-coverage": "a pinned serve-path module fell out of strict "
                       "no-print coverage (or vanished)",
}

STRICT_DIRS = ("rtap_tpu/service/", "rtap_tpu/obs/",
               "rtap_tpu/resilience/", "rtap_tpu/ingest/",
               "rtap_tpu/correlate/", "rtap_tpu/fleet/")

#: coverage pin: serve-path instrumentation modules that MUST live under
#: a strict dir. Extend with every new serve-path module.
MUST_BE_STRICT = (
    "rtap_tpu/obs/latency.py",
    "rtap_tpu/obs/slo.py",
    "rtap_tpu/obs/metrics.py",
    "rtap_tpu/service/loop.py",
    "rtap_tpu/fleet/member.py",
    "rtap_tpu/fleet/aggregator.py",
    "rtap_tpu/fleet/control.py",
)


def _allowed_outside_strict(call: ast.Call) -> bool:
    for kw in call.keywords:
        if kw.arg == "file":
            return True  # explicit stream: stderr diagnostics
    if len(call.args) == 1 and isinstance(call.args[0], ast.Call):
        f = call.args[0].func
        if isinstance(f, ast.Attribute) and f.attr in ("dumps", "to_json"):
            return True  # the one-JSON-line stdout artifact contract
    return False


def run(ctx: AnalysisContext) -> list[Finding]:
    out: list[Finding] = []
    paths = {f.path for f in ctx.files}
    for p in MUST_BE_STRICT:
        if p not in paths:
            out.append(Finding(
                rule="strict-coverage", path=p, line=1, symbol=p,
                message="pinned strict module missing — if it moved, "
                        "update MUST_BE_STRICT (rtap_tpu/analysis/"
                        "prints.py) so no-print coverage follows it"))
        elif not any(p.startswith(d) for d in STRICT_DIRS):
            out.append(Finding(
                rule="strict-coverage", path=p, line=1, symbol=p,
                message="pinned module fell out of strict no-print "
                        "coverage"))
    for sf in ctx.files:
        if sf.tree is None:
            continue
        strict = any(sf.path.startswith(d) for d in STRICT_DIRS)
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"):
                continue
            if strict:
                out.append(Finding(
                    rule="print-strict", path=sf.path, line=node.lineno,
                    symbol="print",
                    message="print() in the serve stack — emit through "
                            "rtap_tpu.obs (or logging) instead"))
            elif not _allowed_outside_strict(node):
                out.append(Finding(
                    rule="print-bare", path=sf.path, line=node.lineno,
                    symbol="print",
                    message="bare print() — route to stderr (file=) or "
                            "emit a JSON artifact line"))
    return out
