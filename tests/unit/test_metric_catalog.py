"""Metric-catalog drift gate (ISSUE 6 satellite).

docs/TELEMETRY.md is the operator-facing catalog of every `rtap_obs_*`
instrument; it went stale twice in past PRs before anyone noticed.
This gate makes drift a test failure in BOTH directions:

- every metric name registered in code (rtap_tpu/, scripts/)
  must appear in docs/TELEMETRY.md, and
- every metric name the catalog's tables document must exist in code
  (a doc row for a deleted metric is a lie operators will alert on).

Names are extracted as string literals — the codebase registers every
instrument with a literal name (a dynamically-built name would also be
un-greppable for operators, so the convention is load-bearing).
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_NAME = re.compile(r'"(rtap_obs_[a-z0-9_]+)"')
_DOC_NAME = re.compile(r"rtap_obs_[a-z0-9_]+")
# catalog table rows: | `rtap_obs_...` | type | ...
_DOC_ROW = re.compile(r"^\|\s*`(rtap_obs_[a-z0-9_]+)`", re.MULTILINE)


def _code_names() -> set[str]:
    names: set[str] = set()
    roots = [os.path.join(REPO, "rtap_tpu"), os.path.join(REPO, "scripts")]
    files = []
    for root in roots:
        for dirpath, _dirs, fns in os.walk(root):
            files.extend(os.path.join(dirpath, fn)
                         for fn in fns if fn.endswith(".py"))
    for path in files:
        with open(path, encoding="utf-8") as f:
            names.update(_NAME.findall(f.read()))
    return names


def _doc_text() -> str:
    with open(os.path.join(REPO, "docs", "TELEMETRY.md"),
              encoding="utf-8") as f:
        return f.read()


@pytest.mark.quick
def test_every_registered_metric_is_documented():
    code = _code_names()
    assert code, "metric literal scan found nothing — the gate is broken"
    documented = set(_DOC_NAME.findall(_doc_text()))
    missing = sorted(code - documented)
    assert not missing, (
        f"metrics registered in code but absent from docs/TELEMETRY.md: "
        f"{missing} — add a catalog row (docs/TELEMETRY.md 'Adding a "
        "metric')")


@pytest.mark.quick
def test_every_documented_metric_exists_in_code():
    code = _code_names()
    rows = set(_DOC_ROW.findall(_doc_text()))
    assert rows, "catalog table scan found nothing — the gate is broken"
    stale = sorted(rows - code)
    assert not stale, (
        f"docs/TELEMETRY.md documents metrics no code registers: {stale} "
        "— drop the stale rows (or restore the instrument)")
