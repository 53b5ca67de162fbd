"""Analyzer framework: findings, suppressions, baseline, the runner.

The contracts this package enforces are the ones three separate review
passes kept re-discovering by hand (ISSUE 12): lock discipline across the
daemon-threaded serve modules, hot-path purity (device code must stay
deterministic and fetch-free; presence checks are not-NaN, never
isfinite), exception discipline in the serve stack, flag↔docs drift, and
the print gate. Each invariant is a *pass* (one module under
``rtap_tpu/analysis/``) producing :class:`Finding`s; this module owns
everything shared — file discovery/parsing, the per-finding suppression
comments, the committed baseline for grandfathered findings, and the
report the CLI renders.

Suppression syntax (docs/ANALYSIS.md):

    some_code()  # rtap: allow[rule-id] — one-line justification

A suppression covers findings of that rule on its own line and on the
line directly below (so a comment-only line can annotate the statement
it precedes). Several rules separate with commas:
``# rtap: allow[race,except-silent] — why``.

Baseline (``analysis_baseline.json`` at the repo root): grandfathered
findings keyed by ``(rule, path, symbol)`` — symbols are stable
(``Class.attr``, ``func:except OSError#2``), never line numbers, so
unrelated edits don't churn the file. Every entry MUST carry a
non-empty ``why``; a why-less entry is itself a finding. Entries that
no longer match anything are reported as stale (non-fatal — delete
them when you see them).
"""

from __future__ import annotations

import ast
import hashlib
import io
import json
import os
import re
import time
import tokenize
from dataclasses import dataclass, field

__all__ = [
    "AnalysisContext",
    "Baseline",
    "Finding",
    "Report",
    "SourceFile",
    "discover_files",
    "render_human",
    "run_analysis",
]

#: the suppression comment grammar (see module docstring)
_SUPPRESS_RE = re.compile(r"#\s*rtap:\s*allow\[([A-Za-z0-9_,\s-]+)\]")

#: default baseline filename at the analysis root
BASELINE_NAME = "analysis_baseline.json"

#: the --json artifact's schema version. Bump on any shape change to
#: the artifact dict — soaks archive these lines across
#: months and the reader must be able to dispatch on shape. v3
#: (ISSUE 14): cache gains the "warm" mode (pass-partitioned partial
#: reuse) and per_pass covers the device-kernel pass family. v4
#: (ISSUE 15): the mesh-readiness pass family lands (partition-contract,
#: device-scope, collective-discipline, shard-resource, scaling-math)
#: and SCALING.md joins the analyzer inputs.
SCHEMA_VERSION = 4

#: default findings-cache filename at the analysis root (gitignored)
CACHE_NAME = ".rtap_lint_cache.json"

#: bump to orphan every existing cache when the cache format changes
#: (2: ISSUE 14 — per-file pass partition section added; 3: ISSUE 15 —
#: SCALING.md hash joins the key)
_CACHE_FORMAT = 3

#: gate-critical rules that neither inline suppressions nor the baseline
#: may silence — the print gate is plumbing other gates stand on, and a
#: suppressible guard is no guard (the canary tests pin this)
NON_SUPPRESSIBLE = frozenset({"print-strict", "strict-coverage",
                              "parse-error"})


@dataclass
class Finding:
    """One invariant violation at one site."""

    rule: str          # pass rule id, e.g. "race", "except-silent"
    path: str          # repo-relative posix path
    line: int          # 1-based line of the offending node
    symbol: str        # stable key within the file (line-insensitive)
    message: str       # human explanation with the fix direction

    def key(self) -> tuple[str, str, str]:
        return (self.rule, self.path, self.symbol)

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "symbol": self.symbol, "message": self.message}


class SourceFile:
    """One parsed python file + its suppression comments.

    ``path`` is repo-relative (posix separators) — it decides pass scope
    (tests build synthetic paths to land fixture snippets in scope).
    Files that fail to parse record ``parse_error`` instead of a tree;
    the runner turns that into a finding (compileall would catch it too,
    but the analyzer must never crash on a torn working tree).
    """

    def __init__(self, path: str, text: str):
        self.path = path.replace(os.sep, "/")
        self.text = text
        self.lines = text.splitlines()
        self.parse_error: str | None = None
        try:
            self.tree: ast.AST | None = ast.parse(text, filename=path)
        except SyntaxError as e:
            self.tree = None
            self.parse_error = f"{type(e).__name__}: {e}"
        self._suppressions: dict[int, set[str]] | None = None

    @property
    def suppressions(self) -> dict[int, set[str]]:
        """line -> rule ids suppressed there. Comments live outside the
        AST, so tokenize finds them (including trailing ones) — LAZILY:
        only files that actually have findings pay the tokenize pass
        (~half the parse cost fleet-wide, and most files have none)."""
        if self._suppressions is None:
            self._suppressions = {}
            if self.parse_error is None and "rtap:" in self.text:
                try:
                    for tok in tokenize.generate_tokens(
                            io.StringIO(self.text).readline):
                        if tok.type != tokenize.COMMENT:
                            continue
                        m = _SUPPRESS_RE.search(tok.string)
                        if m is None:
                            continue
                        rules = {r.strip() for r in m.group(1).split(",")
                                 if r.strip()}
                        self._suppressions.setdefault(
                            tok.start[0], set()).update(rules)
                except tokenize.TokenError:
                    pass  # ast accepted it; worst case this file's
                    # suppression comments are not honored (fails loud)
        return self._suppressions

    def suppressed(self, rule: str, line: int) -> bool:
        """A finding is suppressed by a comment on its line or on the
        line directly above (the comment-on-its-own-line form)."""
        for ln in (line, line - 1):
            if rule in self.suppressions.get(ln, ()):
                return True
        return False


@dataclass
class AnalysisContext:
    """Everything a pass may consult."""

    root: str
    files: list[SourceFile]
    #: README + docs/**.md concatenated (flag↔docs pass); lazily loaded,
    #: overridable by tests
    docs_text: str | None = None
    #: tests/parity/**.py concatenated (twin-parity pass — deleting a
    #: parity test must re-fail the gate, so the parity tree is an
    #: analyzer INPUT and rides the cache key like the docs text)
    parity_text: str | None = None
    #: SCALING.md at the repo root (scaling-math pass, ISSUE 15: the
    #: quoted bytes/stream numbers are cross-checked against a static
    #: derivation from the config dataclasses — editing the doc must
    #: re-run the pass, so it is an analyzer INPUT like the docs text)
    scaling_text: str | None = None

    def files_under(self, *prefixes: str) -> list[SourceFile]:
        return [f for f in self.files
                if any(f.path.startswith(p) for p in prefixes)]

    def file(self, path: str) -> SourceFile | None:
        for f in self.files:
            if f.path == path:
                return f
        return None

    def docs(self) -> str:
        # ONE loader shared with the cache key (_docs_text): the flags
        # pass must analyze exactly the text the cache hashed, or a
        # docs-only edit could be served a stale green hit
        if self.docs_text is None:
            self.docs_text = _docs_text(self.root)
        return self.docs_text

    def parity(self) -> str:
        # same single-loader discipline as docs(): the twin-parity pass
        # must see exactly the text the cache key hashed
        if self.parity_text is None:
            self.parity_text = _parity_text(self.root)
        return self.parity_text

    def scaling(self) -> str:
        # same single-loader discipline again (scaling-math pass)
        if self.scaling_text is None:
            self.scaling_text = _scaling_text(self.root)
        return self.scaling_text


class Baseline:
    """The committed grandfathered-findings file (see module docstring)."""

    def __init__(self, entries: list[dict], path: str | None = None):
        self.path = path
        self.entries = entries
        self.format_errors: list[str] = []
        self._index: dict[tuple[str, str, str], dict] = {}
        self._used: set[tuple[str, str, str]] = set()
        for i, e in enumerate(entries):
            rule, p, sym = (e.get("rule"), e.get("path"), e.get("symbol"))
            if not (rule and p and sym):
                self.format_errors.append(
                    f"entry #{i} missing rule/path/symbol: {e!r}")
                continue
            if not str(e.get("why", "")).strip():
                self.format_errors.append(
                    f"entry #{i} ({rule}:{p}:{sym}) has no 'why' — every "
                    "baseline entry must carry a justification")
                continue
            self._index[(rule, p, sym)] = e

    @classmethod
    def load(cls, path: str) -> "Baseline":
        try:
            with open(path, encoding="utf-8") as fh:
                data = json.load(fh)
        except FileNotFoundError:
            return cls([], path)
        except (OSError, ValueError) as e:
            b = cls([], path)
            b.format_errors.append(f"unreadable baseline {path}: {e}")
            return b
        entries = data.get("entries", []) if isinstance(data, dict) else []
        if not isinstance(entries, list):
            b = cls([], path)
            b.format_errors.append(
                f"baseline {path}: 'entries' must be a list")
            return b
        return cls(entries, path)

    def matches(self, finding: Finding) -> bool:
        k = finding.key()
        if k in self._index:
            self._used.add(k)
            return True
        return False

    def stale_entries(self) -> list[dict]:
        return [e for k, e in sorted(self._index.items())
                if k not in self._used]


def discover_texts(root: str) -> list[tuple[str, str]]:
    """(repo-relative path, text) for the analysis surface: every .py
    under rtap_tpu/ and scripts/ — the set the old check_static.sh
    walked, so the print gate's coverage is unchanged.
    Split from parsing so the findings cache can judge freshness from
    content hashes WITHOUT paying ~100 ast.parse calls on a hit."""
    out: list[tuple[str, str]] = []
    for top in ("rtap_tpu", "scripts"):
        base = os.path.join(root, top)
        for dirpath, dirnames, filenames in os.walk(base):
            # sorted: os.walk's subdir order is filesystem-arbitrary,
            # and the whole-program model's first-definition-wins (and
            # finding/report order generally) must not vary across
            # hosts — the analyzer holds itself to its own
            # replay-determinism rule
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__")
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fn)
                rel = os.path.relpath(full, root)
                with open(full, encoding="utf-8") as fh:
                    out.append((rel, fh.read()))
    return out


def discover_files(root: str) -> list[SourceFile]:
    return [SourceFile(p, t) for p, t in discover_texts(root)]


@dataclass
class Report:
    """The runner's result: what the CLI renders and the gate asserts."""

    findings: list[Finding]          # unsuppressed, the gate's subject
    suppressed: list[Finding]        # silenced by inline comments
    baselined: list[Finding]         # silenced by the baseline file
    stale_baseline: list[dict]       # baseline entries matching nothing
    baseline_errors: list[str]       # malformed baseline entries (fatal)
    per_pass: dict = field(default_factory=dict)  # pass -> raw count
    elapsed_s: float = 0.0
    files_scanned: int = 0
    #: "cold" (full run, cache written), "hit" (replayed from the
    #: content-hash cache), "warm" (per-file passes reused for the
    #: unchanged files, whole-program passes re-run — ISSUE 14), "off"
    #: (cache not engaged: fixtures, --rules subsets, --no-cache)
    cache_mode: str = "off"

    @property
    def ok(self) -> bool:
        return not self.findings and not self.baseline_errors

    def to_dict(self) -> dict:
        """The --json artifact line (soaks archive this)."""
        return {
            "analysis": {
                "schema_version": SCHEMA_VERSION,
                "ok": self.ok,
                "files_scanned": self.files_scanned,
                "elapsed_s": round(self.elapsed_s, 3),
                "cache": self.cache_mode,
                "findings": [f.to_dict() for f in self.findings],
                "suppressed": len(self.suppressed),
                "baselined": len(self.baselined),
                "stale_baseline": self.stale_baseline,
                "baseline_errors": self.baseline_errors,
                "per_pass": dict(sorted(self.per_pass.items())),
            }
        }


# --------------------------------------------------------------- cache --
# The findings cache, pass-PARTITIONED since ISSUE 14. Whole-program
# passes (lock-order, cross-share, twin-parity, donation,
# wire-contract) make per-file findings reuse unsound for THEM — one
# edited file can add or remove an edge whose finding anchors in
# another file — so they stay all-or-nothing. Per-file passes
# (PARTITION = "file": races, purity, excepts, determinism, lifecycle,
# trace-safety, static-hash, dtype-domain) produce findings that
# depend only on one file's bytes, so the cache additionally stores
# their raw findings PER FILE and replays them for every unchanged
# file while only the edited files re-run — the "warm" mode that keeps
# incremental runs ~2 s with the full pass family live. The exact-hit
# fast path is unchanged: when EVERY input is byte-identical (file
# hashes, docs text, parity-test text, baseline, analyzer sources) the
# classified report replays with no parsing at all. Classification
# (suppressions/baseline) is always re-derived from raw findings — a
# baseline edit must never be served a stale verdict. All three modes
# are finding-identical by test (tests/unit/test_static_checks.py).

def _sha(text: str) -> str:
    return hashlib.sha256(text.encode("utf-8", "replace")).hexdigest()[:20]


def _analyzer_fingerprint() -> str:
    """Hash of the analysis package's own sources: editing a pass must
    orphan the cache, or a tightened rule would silently not re-run."""
    here = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    for fn in sorted(os.listdir(here)):
        if fn.endswith(".py"):
            with open(os.path.join(here, fn), "rb") as fh:
                h.update(fn.encode() + b"\0")
                h.update(fh.read() + b"\0")
    return h.hexdigest()[:20]


def _docs_text(root: str) -> str:
    chunks = []
    p = os.path.join(root, "README.md")
    if os.path.isfile(p):
        with open(p, encoding="utf-8") as fh:
            chunks.append(fh.read())
    docs_dir = os.path.join(root, "docs")
    if os.path.isdir(docs_dir):
        for fn in sorted(os.listdir(docs_dir)):
            if fn.endswith(".md"):
                with open(os.path.join(docs_dir, fn),
                          encoding="utf-8") as fh:
                    chunks.append(fh.read())
    return "\n".join(chunks)


def _parity_text(root: str) -> str:
    """tests/parity/**.py concatenated — the twin-parity pass's
    test-coverage evidence (and a cache-key input for the same reason
    the docs text is)."""
    chunks = []
    pdir = os.path.join(root, "tests", "parity")
    if os.path.isdir(pdir):
        for fn in sorted(os.listdir(pdir)):
            if fn.endswith(".py"):
                with open(os.path.join(pdir, fn),
                          encoding="utf-8") as fh:
                    chunks.append(fh.read())
    return "\n".join(chunks)


def _scaling_text(root: str) -> str:
    """SCALING.md at the repo root — the scaling-math pass's
    cross-check subject (and a cache-key input for the same reason the
    docs text is; it lives at the root, outside _docs_text's walk)."""
    p = os.path.join(root, "SCALING.md")
    if os.path.isfile(p):
        with open(p, encoding="utf-8") as fh:
            return fh.read()
    return ""


def _cache_key(texts: list[tuple[str, str]], docs: str, parity: str,
               scaling: str, baseline_path: str) -> dict:
    try:
        with open(baseline_path, encoding="utf-8") as fh:
            baseline_hash = _sha(fh.read())
    except OSError:
        baseline_hash = "absent"
    return {
        "format": _CACHE_FORMAT,
        "analyzer": _analyzer_fingerprint(),
        "files": {p: _sha(t) for p, t in texts},
        "docs": _sha(docs),
        "parity": _sha(parity),
        "scaling": _sha(scaling),
        "baseline": baseline_hash,
    }


def _report_to_cache(report: Report) -> dict:
    return {
        "findings": [f.to_dict() for f in report.findings],
        "suppressed": [f.to_dict() for f in report.suppressed],
        "baselined": [f.to_dict() for f in report.baselined],
        "stale_baseline": report.stale_baseline,
        "baseline_errors": report.baseline_errors,
        "per_pass": report.per_pass,
        "files_scanned": report.files_scanned,
    }


def _report_from_cache(data: dict, elapsed_s: float) -> Report:
    def fs(key):
        return [Finding(**d) for d in data[key]]

    return Report(
        findings=fs("findings"), suppressed=fs("suppressed"),
        baselined=fs("baselined"),
        stale_baseline=data["stale_baseline"],
        baseline_errors=data["baseline_errors"],
        per_pass=data["per_pass"], elapsed_s=elapsed_s,
        files_scanned=data["files_scanned"], cache_mode="hit")


def run_analysis_cached(root: str, baseline_path: str | None = None,
                        cache_path: str | None = None) -> Report:
    """The CLI's full-run entry point. Three speeds:

    * **hit** — every input byte-identical: replay the classified
      report, no parsing at all;
    * **warm** — same analyzer, some files changed: per-file passes
      re-run only on the changed files (cached raw findings replayed
      for the rest), whole-program passes re-run in full;
    * **cold** — no usable cache (format/analyzer change, first run).

    ``--rules`` subsets and fixture contexts never come through here —
    the cache only ever holds full-tree reports."""
    from rtap_tpu.analysis import PASSES

    t0 = time.perf_counter()
    baseline_path = baseline_path or os.path.join(root, BASELINE_NAME)
    cache_path = cache_path or os.path.join(root, CACHE_NAME)
    texts = discover_texts(root)
    docs = _docs_text(root)
    parity = _parity_text(root)
    scaling = _scaling_text(root)
    key = _cache_key(texts, docs, parity, scaling, baseline_path)
    try:
        with open(cache_path, encoding="utf-8") as fh:
            cached = json.load(fh)
    except (OSError, ValueError):
        cached = None
    if isinstance(cached, dict) and cached.get("key") == key:
        return _report_from_cache(
            cached["report"], time.perf_counter() - t0)

    # ---- partial (warm) reuse: unchanged files keep their per-file-
    # pass raw findings; only edited files pay the per-file passes
    reuse: dict[str, dict] = {}
    if isinstance(cached, dict) and isinstance(cached.get("key"), dict) \
            and cached["key"].get("format") == _CACHE_FORMAT \
            and cached["key"].get("analyzer") == key["analyzer"] \
            and isinstance(cached.get("perfile"), dict):
        old_hashes = cached["key"].get("files", {})
        for p, h in key["files"].items():
            if old_hashes.get(p) == h and p in cached["perfile"]:
                reuse[p] = cached["perfile"][p]

    files = [SourceFile(p, t) for p, t in texts]
    ctx = AnalysisContext(root=root, files=files, docs_text=docs,
                          parity_text=parity, scaling_text=scaling)
    baseline = Baseline.load(baseline_path)
    file_passes = [m for m in PASSES
                   if getattr(m, "PARTITION", "program") == "file"]
    program_passes = [m for m in PASSES if m not in file_passes]

    raw: list[Finding] = []
    per_pass: dict[str, int] = {m.PASS_NAME: 0 for m in PASSES}
    pass_of = {rid: m.PASS_NAME for m in file_passes for rid in m.RULES}
    perfile: dict[str, dict] = {}
    changed = [f for f in files if f.path not in reuse]
    sub = AnalysisContext(root=root, files=changed, docs_text=docs,
                          parity_text=parity, scaling_text=scaling)
    fresh_raw, fresh_counts = _run_passes(sub, file_passes)
    for p, n in fresh_counts.items():
        per_pass[p] += n
    for f in changed:
        perfile[f.path] = {}
    for fi in fresh_raw:
        perfile.setdefault(fi.path, {}).setdefault(
            pass_of.get(fi.rule, fi.rule), []).append(fi.to_dict())
        raw.append(fi)
    for path, bucket in reuse.items():
        perfile[path] = bucket
        for pname, dicts in bucket.items():
            per_pass[pname] = per_pass.get(pname, 0) + len(dicts)
            raw.extend(Finding(**d) for d in dicts)
    prog_raw, prog_counts = _run_passes(ctx, program_passes)
    per_pass.update(prog_counts)
    raw.extend(prog_raw)

    report = _classify(raw, ctx, baseline, rules=None,
                       per_pass=per_pass)
    report.elapsed_s = time.perf_counter() - t0
    report.cache_mode = "warm" if reuse else "cold"
    tmp = f"{cache_path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"key": key, "report": _report_to_cache(report),
                       "perfile": perfile}, fh)
        os.replace(tmp, cache_path)
    except OSError:
        # an unwritable cache (read-only checkout) costs the NEXT run
        # its speedup, never this run its correctness
        try:
            os.remove(tmp)
        except OSError:
            pass
    return report


def _run_passes(ctx: AnalysisContext, passes) -> tuple[list[Finding],
                                                       dict[str, int]]:
    raw: list[Finding] = []
    per_pass: dict[str, int] = {}
    for mod in passes:
        found = mod.run(ctx)
        per_pass[mod.PASS_NAME] = len(found)
        raw.extend(found)
    return raw, per_pass


def _classify(raw: list[Finding], ctx: AnalysisContext,
              baseline: Baseline, rules: set[str] | None,
              per_pass: dict[str, int]) -> Report:
    """Suppression/baseline classification over raw findings (always
    re-derived — cached raw findings must never carry a stale
    verdict). Parse errors are appended here: a file that does not
    parse is a finding too (the analyzer must degrade loudly, not
    crash or silently skip)."""
    raw = list(raw)
    for f in ctx.files:
        if f.parse_error is not None:
            raw.append(Finding(
                rule="parse-error", path=f.path, line=1,
                symbol="module", message=f.parse_error))
    findings: list[Finding] = []
    suppressed: list[Finding] = []
    baselined: list[Finding] = []
    for fi in raw:
        if rules is not None and fi.rule not in rules:
            continue
        sf = ctx.file(fi.path)
        if fi.rule in NON_SUPPRESSIBLE:
            findings.append(fi)
        elif sf is not None and sf.suppressed(fi.rule, fi.line):
            suppressed.append(fi)
        elif baseline.matches(fi):
            baselined.append(fi)
        else:
            findings.append(fi)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    suppressed.sort(key=lambda f: (f.path, f.line, f.rule))
    baselined.sort(key=lambda f: (f.path, f.line, f.rule))
    # staleness is only judgeable on a FULL run: a --rules subset never
    # consults the baseline for the unselected rules, and reporting
    # their (valid) entries as stale would advise deleting them
    return Report(
        findings=findings, suppressed=suppressed, baselined=baselined,
        stale_baseline=baseline.stale_entries() if rules is None else [],
        baseline_errors=list(baseline.format_errors),
        per_pass=per_pass, files_scanned=len(ctx.files))


def run_analysis(root: str, files: list[SourceFile] | None = None,
                 baseline: Baseline | None = None,
                 rules: set[str] | None = None,
                 ctx: AnalysisContext | None = None) -> Report:
    """Run every pass over the tree; classify findings against inline
    suppressions and the baseline. `files`/`ctx` injection is the unit
    tests' fixture seam; `rules` filters to a subset of rule ids."""
    from rtap_tpu.analysis import PASSES

    t0 = time.perf_counter()
    if ctx is None:
        if files is None:
            files = discover_files(root)
        ctx = AnalysisContext(root=root, files=files)
    if baseline is None:
        baseline = Baseline.load(os.path.join(root, BASELINE_NAME))
    raw, per_pass = _run_passes(ctx, PASSES)
    report = _classify(raw, ctx, baseline, rules, per_pass)
    report.elapsed_s = time.perf_counter() - t0
    return report


def render_human(report: Report) -> str:
    """The stderr report: one line per finding, then the tallies."""
    lines: list[str] = []
    for f in report.findings:
        lines.append(f"{f.path}:{f.line}: [{f.rule}] {f.symbol}: "
                     f"{f.message}")
    for e in report.baseline_errors:
        lines.append(f"analysis_baseline.json: [baseline-format] {e}")
    for e in report.stale_baseline:
        lines.append(
            f"analysis_baseline.json: stale entry "
            f"{e.get('rule')}:{e.get('path')}:{e.get('symbol')} matches "
            "nothing — delete it (non-fatal)")
    lines.append(
        f"rtap-lint: {len(report.findings)} finding(s), "
        f"{len(report.suppressed)} suppressed, "
        f"{len(report.baselined)} baselined, "
        f"{report.files_scanned} files in {report.elapsed_s:.2f}s "
        f"({'OK' if report.ok else 'FAIL'})")
    return "\n".join(lines)
