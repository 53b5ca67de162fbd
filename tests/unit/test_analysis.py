"""rtap-lint (rtap_tpu/analysis, ISSUE 12): per-pass fixture coverage.

Every pass gets a positive (deliberately-bad snippet fails), a negative
(idiomatic-good snippet passes), and a suppressed fixture (the inline
``# rtap: allow[rule]`` comment silences exactly that rule) — mirroring
the print-gate canary discipline of test_static_checks.py, but at the
library layer (in-memory SourceFiles, no subprocess) so the whole file
stays fast. Baseline mechanics (match / why-less entry / stale entry)
are covered here too; the end-to-end gate (real repo, real baseline,
wall budget, --json artifact) lives in test_static_checks.py.
"""

import pytest

from rtap_tpu.analysis import run_analysis
from rtap_tpu.analysis.core import (
    AnalysisContext,
    Baseline,
    Finding,
    SourceFile,
)
from rtap_tpu.analysis.prints import MUST_BE_STRICT

pytestmark = pytest.mark.quick


def lint(path, code, rules=None, docs="", extra=(), baseline=None):
    """Run the analyzer over in-memory fixtures, filtered to `rules`
    (None = a full run, as the gate does it)."""
    files = [SourceFile(path, code)]
    files += [SourceFile(p, c) for p, c in extra]
    ctx = AnalysisContext(root="/__fixture__", files=files, docs_text=docs)
    return run_analysis("/__fixture__", baseline=baseline or Baseline([]),
                        rules=set(rules) if rules is not None else None,
                        ctx=ctx)


#: stubs for the MUST_BE_STRICT pin so full (rules=None) fixture runs
#: don't trip strict-coverage on the synthetic context
PIN_STUBS = tuple((p, "x = 1\n") for p in MUST_BE_STRICT)


def rules_of(report):
    return sorted({f.rule for f in report.findings})


# ---------------------------------------------------------------- races --
RACY = """
import threading

class Racy:
    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()

    def start(self):
        threading.Thread(target=self._run, name="rtap-t", daemon=True).start()

    def _run(self):
        self.n += 1

    def bump(self):
        with self._lock:
            self.n += 1
"""

GUARDED = RACY.replace(
    "    def _run(self):\n        self.n += 1\n",
    "    def _run(self):\n        with self._lock:\n            self.n += 1\n")


def test_race_positive_and_symbol():
    r = lint("rtap_tpu/obs/_fx.py", RACY, ["race"])
    assert [f.symbol for f in r.findings] == ["Racy.n"]
    assert not r.ok


def test_race_negative_when_both_sides_guarded():
    r = lint("rtap_tpu/obs/_fx.py", GUARDED, ["race"])
    assert r.findings == [] and r.ok


def test_race_out_of_scope_dir_ignored():
    # models/ is not serve stack — the pass only covers the strict dirs
    r = lint("rtap_tpu/models/_fx.py", RACY, ["race"])
    assert r.findings == []


def test_race_suppression_comment():
    code = RACY.replace(
        "        self.n += 1\n\n    def bump",
        "        self.n += 1  # rtap: allow[race] — test tolerance\n\n"
        "    def bump")
    r = lint("rtap_tpu/obs/_fx.py", code, ["race"])
    assert r.findings == [] and len(r.suppressed) == 1


def test_race_interprocedural_guard_inheritance():
    """A private method whose EVERY call site (both sides) holds the
    lock inherits the guard — the BinaryBatchSource._apply idiom."""
    code = """
import threading

class C:
    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()

    def start(self):
        threading.Thread(target=self._run, name="rtap-t").start()

    def _run(self):
        with self._lock:
            self._bump()

    def public(self):
        with self._lock:
            self._bump()

    def _bump(self):
        self.n += 1
"""
    r = lint("rtap_tpu/ingest/_fx.py", code, ["race"])
    assert r.findings == []
    # ... but one unlocked call path from either side breaks the
    # inheritance (intersection over paths, not union)
    leaky = code.replace(
        "    def public(self):\n        with self._lock:\n"
        "            self._bump()\n",
        "    def public(self):\n        self._bump()\n")
    r2 = lint("rtap_tpu/ingest/_fx.py", leaky, ["race"])
    assert [f.symbol for f in r2.findings] == ["C.n"]


def test_race_nested_thread_target_function():
    """The Lease.start_heartbeat idiom: a nested function handed to
    Thread(target=...) is thread-side code."""
    code = """
import threading

class C:
    def __init__(self):
        self.state = 0
        self._lock = threading.Lock()

    def go(self):
        def _beat():
            self.state = 1
        threading.Thread(target=_beat, name="rtap-t").start()

    def poke(self):
        self.state = 2
"""
    r = lint("rtap_tpu/resilience/_fx.py", code, ["race"])
    assert [f.symbol for f in r.findings] == ["C.state"]


def test_race_request_handler_self_concurrency():
    """A nested RequestHandler class runs one thread PER CONNECTION:
    an unguarded write to an outer attr races with ITSELF — the
    TcpJsonlSource._py_parse_errors lost-update class."""
    code = """
import socketserver
import threading

class Src:
    def __init__(self):
        self.errors = 0
        self._lock = threading.Lock()
        outer = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                outer.errors += 1
"""
    r = lint("rtap_tpu/service/_fx.py", code, ["race"])
    assert [f.symbol for f in r.findings] == ["Src.errors"]
    guarded = code.replace(
        "                outer.errors += 1",
        "                with outer._lock:\n"
        "                    outer.errors += 1")
    assert lint("rtap_tpu/service/_fx.py", guarded, ["race"]).findings == []


def test_race_init_writes_are_construction_time():
    """__init__ runs before any thread exists: a thread-side writer plus
    only-__init__ main writes is single-writer, not a race."""
    code = """
import threading

class C:
    def __init__(self):
        self.n = 0

    def start(self):
        threading.Thread(target=self._run, name="rtap-t").start()

    def _run(self):
        self.n += 1
"""
    r = lint("rtap_tpu/obs/_fx.py", code, ["race"])
    assert r.findings == []


def test_thread_name_rule():
    anon = ("import threading\n"
            "t = threading.Thread(target=print, daemon=True)\n")
    r = lint("rtap_tpu/obs/_fx.py", anon, ["thread-name"])
    assert rules_of(r) == ["thread-name"]
    named = anon.replace("daemon=True", 'daemon=True, name="rtap-x-y"')
    assert lint("rtap_tpu/obs/_fx.py", named, ["thread-name"]).findings == []
    offform = anon.replace("daemon=True", 'daemon=True, name="worker"')
    assert len(lint("rtap_tpu/obs/_fx.py", offform,
                    ["thread-name"]).findings) == 1
    # out of the serve stack: utils/ threads are not gated
    assert lint("rtap_tpu/utils/_fx.py", anon, ["thread-name"]).findings == []


# --------------------------------------------------------------- purity --
def test_purity_nondet_in_ops():
    code = "import time\n\ndef kernel(x):\n    return x + time.time()\n"
    r = lint("rtap_tpu/ops/_fx.py", code, ["purity-nondet"])
    assert rules_of(r) == ["purity-nondet"]
    # the loop module may read the wall clock (it IS the pacer)...
    assert lint("rtap_tpu/service/loop.py", code,
                ["purity-nondet"]).findings == []
    # ...but never mint randomness mid-path
    rnd = "import random\n\ndef f():\n    return random.random()\n"
    assert len(lint("rtap_tpu/service/loop.py", rnd,
                    ["purity-nondet"]).findings) == 1
    # keyed jax.random is deterministic and exempt everywhere
    jr = "import jax\n\ndef f(k):\n    return jax.random.uniform(k)\n"
    assert lint("rtap_tpu/ops/_fx.py", jr, ["purity-nondet"]).findings == []


def test_purity_fetch_only_in_tracing_functions():
    fetch = ("import numpy as np\nimport jax.numpy as jnp\n\n"
             "def kernel(x):\n    y = jnp.sum(x)\n"
             "    return np.asarray(y)\n")
    r = lint("rtap_tpu/ops/_fx.py", fetch, ["purity-fetch"])
    assert rules_of(r) == ["purity-fetch"]
    item = ("import jax.numpy as jnp\n\n"
            "def kernel(x):\n    return jnp.sum(x).item()\n")
    assert len(lint("rtap_tpu/ops/_fx.py", item,
                    ["purity-fetch"]).findings) == 1
    # a pure-numpy host twin is out of the rule by construction
    twin = ("import numpy as np\n\n"
            "def host_twin(x):\n    return np.asarray(x).sum()\n")
    assert lint("rtap_tpu/ops/_fx.py", twin, ["purity-fetch"]).findings == []


def test_purity_isfinite_presence_contract():
    code = ("import numpy as np\n\n"
            "def merge(vec):\n    return vec[np.isfinite(vec)]\n")
    r = lint("rtap_tpu/ingest/_fx.py", code, ["purity-isfinite"])
    assert rules_of(r) == ["purity-isfinite"]
    # model-layer encoders keep their deliberate isfinite semantics
    assert lint("rtap_tpu/ops/_fx.py", code,
                ["purity-isfinite"]).findings == []
    ok = code.replace("np.isfinite(vec)", "~np.isnan(vec)")
    assert lint("rtap_tpu/ingest/_fx.py", ok,
                ["purity-isfinite"]).findings == []
    supp = code.replace(
        "np.isfinite(vec)]",
        "np.isfinite(vec)]  # rtap: allow[purity-isfinite] — fixture")
    r3 = lint("rtap_tpu/ingest/_fx.py", supp, ["purity-isfinite"])
    assert r3.findings == [] and len(r3.suppressed) == 1


# -------------------------------------------------------------- excepts --
def test_except_silent_positive_negative_suppressed():
    bad = ("def f(path):\n    try:\n        load(path)\n"
           "    except Exception:\n        pass\n")
    r = lint("rtap_tpu/service/_fx.py", bad, ["except-silent"])
    assert rules_of(r) == ["except-silent"]
    assert "f:except Exception" in r.findings[0].symbol
    # binding an outcome is handling
    ok = bad.replace("        pass\n", "        result = None\n")
    assert lint("rtap_tpu/service/_fx.py", ok,
                ["except-silent"]).findings == []
    # the cleanup carve-out: single teardown call + OSError family
    cleanup = ("def f(sock):\n    try:\n        sock.close()\n"
               "    except OSError:\n        pass\n")
    assert lint("rtap_tpu/service/_fx.py", cleanup,
                ["except-silent"]).findings == []
    # ... but a broad catch does NOT get the carve-out
    broad = cleanup.replace("except OSError", "except Exception")
    assert len(lint("rtap_tpu/service/_fx.py", broad,
                    ["except-silent"]).findings) == 1
    supp = bad.replace("    except Exception:",
                       "    except Exception:  # rtap: allow[except-silent]")
    r2 = lint("rtap_tpu/service/_fx.py", supp, ["except-silent"])
    assert r2.findings == [] and len(r2.suppressed) == 1
    # out of the serve stack: no rule
    assert lint("rtap_tpu/models/_fx.py", bad,
                ["except-silent"]).findings == []


# ---------------------------------------------------------------- flags --
_MAIN_FIXTURE = """
import argparse

def build():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers()
    p = sub.add_parser("serve")
    p.add_argument("--documented-flag")
    p.add_argument("--ghost-flag")
    p = sub.add_parser("replay")
    p.add_argument("--replay-only-flag")
"""


def test_flag_docs_drift():
    r = lint("rtap_tpu/__main__.py", _MAIN_FIXTURE, ["flag-docs"],
             docs="serve takes `--documented-flag` (see runbook)")
    assert [f.symbol for f in r.findings] == ["--ghost-flag"]
    # flags of OTHER subcommands are out of this gate's scope
    assert all("--replay-only-flag" != f.symbol for f in r.findings)
    r2 = lint("rtap_tpu/__main__.py", _MAIN_FIXTURE, ["flag-docs"],
              docs="`--documented-flag` and `--ghost-flag`")
    assert r2.findings == []


def test_flag_docs_prefix_is_not_documentation():
    """Word-boundary matching: a documented `--ghost-flag-extra` must
    NOT satisfy the gate for an undocumented `--ghost-flag` (the serve
    surface has ~11 such prefix pairs — the masking this gate exists
    to catch)."""
    r = lint("rtap_tpu/__main__.py", _MAIN_FIXTURE, ["flag-docs"],
             docs="`--documented-flag`; also `--ghost-flag-extra` exists")
    assert [f.symbol for f in r.findings] == ["--ghost-flag"]


# --------------------------------------------------------------- prints --
def test_print_rules_and_non_suppressibility():
    strict = 'import sys\nprint("x", file=sys.stderr)\n'
    r = lint("rtap_tpu/service/_fx.py", strict, ["print-strict"])
    assert rules_of(r) == ["print-strict"]
    # an allow comment must NOT silence the print gate (guard the guard)
    supp = strict.replace(")\n", ")  # rtap: allow[print-strict]\n")
    r2 = lint("rtap_tpu/service/_fx.py", supp, ["print-strict"])
    assert rules_of(r2) == ["print-strict"]
    # outside the serve stack: file= and single-json.dumps are legal,
    # bare stdout is not
    outside = ('import json, sys\nprint("d", file=sys.stderr)\n'
               'print(json.dumps({"a": 1}))\nprint("bare")\n')
    r3 = lint("rtap_tpu/eval/_fx.py", outside, ["print-bare"])
    assert len(r3.findings) == 1 and r3.findings[0].line == 4


def test_strict_coverage_pin():
    # a context missing the pinned modules reports each as out of
    # coverage — the rename/move tripwire
    r = lint("rtap_tpu/eval/_fx.py", "x = 1\n", ["strict-coverage"])
    assert len(r.findings) == len(MUST_BE_STRICT)
    assert all(f.rule == "strict-coverage" for f in r.findings)


# ------------------------------------------------------------- baseline --
def test_baseline_match_whyless_and_stale():
    bad = ("def f(p):\n    try:\n        load(p)\n"
           "    except Exception:\n        pass\n")
    ent = {"rule": "except-silent", "path": "rtap_tpu/service/_fx.py",
           "symbol": "f:except Exception", "why": "fixture legacy"}
    r = lint("rtap_tpu/service/_fx.py", bad, ["except-silent"],
             baseline=Baseline([ent]))
    assert r.ok and len(r.baselined) == 1 and r.stale_baseline == []
    # a why-less entry is itself a gate failure
    whyless = {k: v for k, v in ent.items() if k != "why"}
    r2 = lint("rtap_tpu/service/_fx.py", bad, ["except-silent"],
              baseline=Baseline([whyless]))
    assert not r2.ok and r2.baseline_errors
    # the finding the why-less entry failed to cover is a real finding
    assert len(r2.findings) == 1


def test_baseline_stale_entry_is_nonfatal():
    # staleness is only judged on a FULL run (rules=None), so the
    # fixture context carries the strict-pin stubs
    bad = ("def f(p):\n    try:\n        load(p)\n"
           "    except Exception:\n        pass\n")
    ent = {"rule": "except-silent", "path": "rtap_tpu/service/_fx.py",
           "symbol": "f:except Exception", "why": "fixture legacy"}
    stale = dict(ent, symbol="gone:except OSError")
    r = lint("rtap_tpu/service/_fx.py", bad, extra=PIN_STUBS,
             baseline=Baseline([ent, stale]))
    assert r.ok and len(r.stale_baseline) == 1
    assert r.stale_baseline[0]["symbol"] == "gone:except OSError"


def test_rules_subset_never_reports_stale_baseline():
    """A --rules subset run skips the baseline for unselected rules, so
    their (valid) entries must NOT be advised stale — only a full run
    can judge staleness."""
    bad = ("def f(p):\n    try:\n        load(p)\n"
           "    except Exception:\n        pass\n")
    ent = {"rule": "except-silent", "path": "rtap_tpu/service/_fx.py",
           "symbol": "f:except Exception", "why": "fixture legacy"}
    r = lint("rtap_tpu/service/_fx.py", bad, ["race"],
             baseline=Baseline([ent]))
    assert r.ok and r.stale_baseline == []


def test_finding_json_shape():
    f = Finding(rule="race", path="a.py", line=3, symbol="C.x",
                message="m")
    d = f.to_dict()
    assert d == {"rule": "race", "path": "a.py", "line": 3,
                 "symbol": "C.x", "message": "m"}


def test_parse_error_is_a_finding():
    r = lint("rtap_tpu/service/_fx.py", "def broken(:\n", ["parse-error"])
    assert rules_of(r) == ["parse-error"]


# ----------------------------------------------------------- lock-order --
LOCK_CYCLE = """
import threading

class C:
    def __init__(self):
        self._a_lock = threading.Lock()
        self._b_lock = threading.Lock()

    def one(self):
        with self._a_lock:
            with self._b_lock:
                pass

    def two(self):
        with self._b_lock:
            with self._a_lock:
                pass
"""


def test_lock_order_cycle_positive_and_canonical_symbol():
    r = lint("rtap_tpu/resilience/_fx.py", LOCK_CYCLE, ["lock-order"])
    assert [f.symbol for f in r.findings] == \
        ["C._a_lock->C._b_lock->C._a_lock"]
    assert not r.ok


def test_lock_order_consistent_nesting_is_clean():
    ordered = LOCK_CYCLE.replace(
        "    def two(self):\n        with self._b_lock:\n"
        "            with self._a_lock:\n",
        "    def two(self):\n        with self._a_lock:\n"
        "            with self._b_lock:\n")
    r = lint("rtap_tpu/resilience/_fx.py", ordered, ["lock-order"])
    assert r.findings == [] and r.ok


def test_lock_order_interprocedural_cycle_through_call():
    """One side nests lexically, the other reaches the reverse order
    through a method call — the acquisition-closure worklist must see
    through the call."""
    code = """
import threading

class C:
    def __init__(self):
        self._a_lock = threading.Lock()
        self._b_lock = threading.Lock()

    def one(self):
        with self._a_lock:
            with self._b_lock:
                pass

    def two(self):
        with self._b_lock:
            self._grab_a()

    def _grab_a(self):
        with self._a_lock:
            pass
"""
    r = lint("rtap_tpu/ingest/_fx.py", code, ["lock-order"])
    assert [f.symbol for f in r.findings] == \
        ["C._a_lock->C._b_lock->C._a_lock"]


def test_lock_order_cross_class_cycle_via_collaborators():
    """The whole-program shape: A holds its lock and calls into B,
    B holds its lock and calls back into A — no single class shows a
    cycle, only the global graph does (constructor-injection typing)."""
    code = """
import threading

class A:
    def __init__(self, b: "B"):
        self._a_lock = threading.Lock()
        self.b = b

    def m(self):
        with self._a_lock:
            self.b.push()

    def poke(self):
        with self._a_lock:
            pass

class B:
    def __init__(self, a: "A"):
        self._b_lock = threading.Lock()
        self.a = a

    def push(self):
        with self._b_lock:
            self.a.poke()
"""
    r = lint("rtap_tpu/obs/_fx.py", code, ["lock-order"])
    # TWO distinct deadlocks live here: the A->B->A ordering cycle
    # (two threads entering from different edges), and the
    # single-thread self-deadlock (A.m's call reaches A.poke, which
    # re-acquires the non-reentrant lock A.m already holds)
    assert sorted(f.symbol for f in r.findings) == \
        ["A._a_lock->A._a_lock", "A._a_lock->B._b_lock->A._a_lock"]
    # ... and breaking one direction (B no longer calls back) is clean
    oneway = code.replace("            self.a.poke()\n",
                          "            pass\n")
    assert lint("rtap_tpu/obs/_fx.py", oneway, ["lock-order"]).findings == []


def test_lock_order_nonreentrant_self_deadlock():
    """Re-acquiring a plain threading.Lock on a path that already holds
    it — the Lease.read-inside-refresh near-miss (PR 8)."""
    code = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()

    def outer(self):
        with self._lock:
            self._inner()

    def _inner(self):
        with self._lock:
            pass
"""
    r = lint("rtap_tpu/resilience/_fx.py", code, ["lock-order"])
    assert [f.symbol for f in r.findings] == ["C._lock->C._lock"]
    # an RLock makes the same nesting legal
    rl = code.replace("threading.Lock()", "threading.RLock()")
    assert lint("rtap_tpu/resilience/_fx.py", rl,
                ["lock-order"]).findings == []


def test_lock_order_self_deadlock_via_collaborator_roundtrip():
    """A holds its plain Lock and calls into B, which calls straight
    back into A re-acquiring the same lock: the re-acquisition is
    reached through a collaborator, so reentrancy must be judged by
    the lock's OWNING class, not the callee."""
    code = """
import threading

class A:
    def __init__(self, b: "B"):
        self._lock = threading.Lock()
        self.b = b

    def m(self):
        with self._lock:
            self.b.push()

    def poke(self):
        with self._lock:
            pass

class B:
    def __init__(self, a: "A"):
        self.a = a

    def push(self):
        self.a.poke()
"""
    r = lint("rtap_tpu/obs/_fx.py", code, ["lock-order"])
    assert [f.symbol for f in r.findings] == ["A._lock->A._lock"]
    # with an RLock the round-trip is legal
    rl = code.replace("threading.Lock()", "threading.RLock()")
    assert lint("rtap_tpu/obs/_fx.py", rl, ["lock-order"]).findings == []


def test_lock_order_explicit_acquire_extends_held_set():
    """self.<lock>.acquire() must contribute ordering edges exactly
    like the with-form: explicit acquire/release code (conditional
    locking) must not bypass the deadlock gate."""
    code = """
import threading

class C:
    def __init__(self):
        self._a_lock = threading.Lock()
        self._b_lock = threading.Lock()

    def one(self):
        self._a_lock.acquire()
        with self._b_lock:
            pass
        self._a_lock.release()

    def two(self):
        with self._b_lock:
            with self._a_lock:
                pass
"""
    r = lint("rtap_tpu/resilience/_fx.py", code, ["lock-order"])
    assert [f.symbol for f in r.findings] == \
        ["C._a_lock->C._b_lock->C._a_lock"]
    # release before the nested acquisition breaks the edge (and the
    # cycle): the held-set tracking honors release, not just acquire
    released = code.replace(
        "        self._a_lock.acquire()\n        with self._b_lock:\n"
        "            pass\n        self._a_lock.release()\n",
        "        self._a_lock.acquire()\n        self._a_lock.release()\n"
        "        with self._b_lock:\n            pass\n")
    assert lint("rtap_tpu/resilience/_fx.py", released,
                ["lock-order"]).findings == []


def test_lock_order_suppression_comment():
    # the cycle finding anchors on the FIRST in-cycle acquisition site
    # (smallest path/line) — that is where the suppression must sit
    supp = LOCK_CYCLE.replace(
        "        with self._a_lock:\n            with self._b_lock:",
        "        with self._a_lock:\n"
        "            # rtap: allow[lock-order] — fixture\n"
        "            with self._b_lock:")
    r = lint("rtap_tpu/resilience/_fx.py", supp, ["lock-order"])
    assert r.findings == [] and len(r.suppressed) == 1


# ---------------------------------------------------------- cross-share --
_TRACKER = """
import threading

class Tracker:
    def __init__(self):
        self.n = 0
        self.samples = {}
        self._lock = threading.Lock()

    def fold(self, k):
        self.samples[k] = self.samples.get(k, 0) + 1
        self.n += 1

    def snapshot(self):
        return dict(self.samples), self.n

class Runner:
    def __init__(self, tracker):
        self.tracker = tracker

    def start(self):
        threading.Thread(target=self._run, name="rtap-t",
                         daemon=True).start()

    def _run(self):
        pass
"""

_WIRE = """
def wire():
    t = Tracker()
    r = Runner(t)
    consume(t)
    return r
"""


def cross_lint(tracker_code, wire_code=_WIRE):
    """Two-module fixture: the tracker lives in obs/, the wiring (and
    the thread-running consumer handoff) in service/ — the pass must
    cross the module boundary to connect them."""
    return lint("rtap_tpu/obs/_fx.py", tracker_code, ["cross-share"],
                extra=(("rtap_tpu/service/_wire.py", wire_code),))


def test_cross_share_positive_across_modules():
    r = cross_lint(_TRACKER)
    assert sorted(f.symbol for f in r.findings) == ["Tracker.n",
                                                    "Tracker.samples"]
    assert "thread-running" in r.findings[0].message


def test_cross_share_guarded_writes_are_clean():
    guarded = _TRACKER.replace(
        "    def fold(self, k):\n"
        "        self.samples[k] = self.samples.get(k, 0) + 1\n"
        "        self.n += 1\n",
        "    def fold(self, k):\n"
        "        with self._lock:\n"
        "            self.samples[k] = self.samples.get(k, 0) + 1\n"
        "            self.n += 1\n")
    assert cross_lint(guarded).findings == []


def test_cross_share_interprocedural_guard_inheritance():
    """A private helper whose every call site holds the lock inherits
    it — the IncidentCorrelator shape that a naive every-method-is-an-
    entry analysis would falsely flag."""
    code = _TRACKER.replace(
        "    def fold(self, k):\n"
        "        self.samples[k] = self.samples.get(k, 0) + 1\n"
        "        self.n += 1\n",
        "    def fold(self, k):\n"
        "        with self._lock:\n"
        "            self._bump(k)\n\n"
        "    def _bump(self, k):\n"
        "        self.samples[k] = self.samples.get(k, 0) + 1\n"
        "        self.n += 1\n")
    assert cross_lint(code).findings == []


def test_cross_share_atomic_rebind_is_the_snapshot_idiom():
    rebind = _TRACKER.replace(
        "        self.samples[k] = self.samples.get(k, 0) + 1\n"
        "        self.n += 1\n",
        "        self.samples = {**self.samples, k: 1}\n")
    assert cross_lint(rebind).findings == []


def test_cross_share_needs_a_threaded_consumer():
    """Handing the tracker to two PLAIN consumers is single-threaded
    wiring — not this pass's business."""
    wire = _WIRE.replace("    r = Runner(t)\n", "    r = consume2(t)\n")
    assert cross_lint(_TRACKER, wire).findings == []


def test_cross_share_suppression_comment():
    supp = _TRACKER.replace(
        "        self.n += 1\n",
        "        self.n += 1  # rtap: allow[cross-share] — fixture\n")
    r = cross_lint(supp)
    assert [f.symbol for f in r.findings] == ["Tracker.samples"]
    assert len(r.suppressed) == 1


# ---------------------------------------------- replay-determinism --
def test_replay_det_set_iteration():
    code = ("def emit(fh):\n"
            "    acc = set()\n"
            "    acc.add(1)\n"
            "    for x in acc:\n"
            "        fh.write(str(x))\n")
    r = lint("rtap_tpu/correlate/_fx.py", code, ["replay-determinism"])
    assert len(r.findings) == 1 and "set-iter" in r.findings[0].symbol
    ok = code.replace("for x in acc:", "for x in sorted(acc):")
    assert lint("rtap_tpu/correlate/_fx.py", ok,
                ["replay-determinism"]).findings == []
    # model/ops code may iterate sets freely — scope is the
    # serialization surface only
    assert lint("rtap_tpu/ops/_fx.py", code,
                ["replay-determinism"]).findings == []


def test_replay_det_self_attr_set_and_comprehension():
    code = ("class J:\n"
            "    def __init__(self):\n"
            "        self._seen = set()\n\n"
            "    def digest(self):\n"
            "        return ''.join(str(x) for x in self._seen)\n")
    r = lint("rtap_tpu/resilience/journal.py", code,
             ["replay-determinism"])
    assert len(r.findings) == 1
    assert "J.digest" in r.findings[0].symbol


def test_replay_det_unsorted_listing():
    code = ("import os\n\n"
            "def walk(d, fh):\n"
            "    for n in os.listdir(d):\n"
            "        fh.write(n)\n")
    r = lint("rtap_tpu/service/checkpoint.py", code,
             ["replay-determinism"])
    assert len(r.findings) == 1 and "fs-iter" in r.findings[0].symbol
    ok = code.replace("os.listdir(d):", "sorted(os.listdir(d)):")
    assert lint("rtap_tpu/service/checkpoint.py", ok,
                ["replay-determinism"]).findings == []
    # Path.iterdir()/glob() method forms count too
    meth = ("def walk(p, fh):\n"
            "    for n in p.iterdir():\n"
            "        fh.write(str(n))\n")
    assert len(lint("rtap_tpu/service/checkpoint.py", meth,
                    ["replay-determinism"]).findings) == 1


def test_replay_det_dict_view_set_ops():
    """a.keys() - b.keys() returns a REAL set (hash-ordered) even
    though iterating a bare .keys() view is insertion-ordered —
    the BinOp branch must treat dict views as set-like."""
    code = ("def diff(a, b, fh):\n"
            "    for k in a.keys() - b.keys():\n"
            "        fh.write(k)\n")
    r = lint("rtap_tpu/correlate/_fx.py", code, ["replay-determinism"])
    assert len(r.findings) == 1 and "set-iter" in r.findings[0].symbol
    # a bare .keys() iteration stays legal (insertion-ordered)
    plain = ("def emit(a, fh):\n"
             "    for k in a.keys():\n"
             "        fh.write(k)\n")
    assert lint("rtap_tpu/correlate/_fx.py", plain,
                ["replay-determinism"]).findings == []


def test_replay_det_float_sum_over_set():
    code = ("def tot(vals):\n"
            "    s = set(vals)\n"
            "    return sum(s)\n")
    r = lint("rtap_tpu/correlate/_fx.py", code, ["replay-determinism"])
    assert len(r.findings) == 1 and "float-sum" in r.findings[0].symbol
    ok = code.replace("sum(s)", "sum(sorted(s))")
    assert lint("rtap_tpu/correlate/_fx.py", ok,
                ["replay-determinism"]).findings == []


def test_replay_det_direct_set_consumption():
    """','.join(set) serializes in hash order with no for-loop for the
    iteration check to see — direct consumption is flagged too."""
    code = ("def emit(fh):\n"
            "    acc = set()\n"
            "    acc.add('x')\n"
            "    fh.write(','.join(acc))\n")
    r = lint("rtap_tpu/correlate/_fx.py", code, ["replay-determinism"])
    assert len(r.findings) == 1
    assert "set-consume" in r.findings[0].symbol
    ok = code.replace("','.join(acc)", "','.join(sorted(acc))")
    assert lint("rtap_tpu/correlate/_fx.py", ok,
                ["replay-determinism"]).findings == []


def test_replay_det_suppression_comment():
    code = ("import os\n\n"
            "def sweep(d):\n"
            "    # rtap: allow[replay-determinism] — all deleted\n"
            "    for n in os.listdir(d):\n"
            "        os.remove(n)\n")
    r = lint("rtap_tpu/service/checkpoint.py", code,
             ["replay-determinism"])
    assert r.findings == [] and len(r.suppressed) == 1


# ---------------------------------------------- resource-lifecycle --
_LEAKY_THREAD = """
import threading

class R:
    def start(self):
        self._t = threading.Thread(target=self._run, name="rtap-x",
                                   daemon=True)
        self._t.start()

    def _run(self):
        pass
"""


def test_lifecycle_thread_without_teardown():
    r = lint("rtap_tpu/obs/_fx.py", _LEAKY_THREAD, ["resource-lifecycle"])
    assert [f.symbol for f in r.findings] == ["R._t"]
    assert "no teardown surface" in r.findings[0].message


def test_lifecycle_bounded_join_is_clean_and_unbounded_flagged():
    closed = _LEAKY_THREAD + (
        "\n    def close(self):\n"
        "        self._t.join(timeout=2.0)\n")
    assert lint("rtap_tpu/obs/_fx.py", closed,
                ["resource-lifecycle"]).findings == []
    unbounded = _LEAKY_THREAD + (
        "\n    def close(self):\n"
        "        self._t.join()\n")
    r = lint("rtap_tpu/obs/_fx.py", unbounded, ["resource-lifecycle"])
    assert [f.symbol for f in r.findings] == ["R._t:unbounded-join"]


def test_lifecycle_release_reached_through_helper():
    """close() -> _stop() -> join: reachability is the in-class call
    closure, not a literal scan of close()'s own body."""
    code = _LEAKY_THREAD + (
        "\n    def close(self):\n"
        "        self._stop()\n"
        "\n    def _stop(self):\n"
        "        self._t.join(timeout=1.0)\n")
    assert lint("rtap_tpu/obs/_fx.py", code,
                ["resource-lifecycle"]).findings == []


def test_lifecycle_socket_and_scope():
    sock = ("import socket\n\n"
            "class S:\n"
            "    def connect(self, addr):\n"
            "        self._sock = socket.create_connection(addr)\n")
    r = lint("rtap_tpu/ingest/_fx.py", sock, ["resource-lifecycle"])
    assert [f.symbol for f in r.findings] == ["S._sock"]
    closed = sock + ("\n    def close(self):\n"
                     "        self._sock.close()\n")
    assert lint("rtap_tpu/ingest/_fx.py", closed,
                ["resource-lifecycle"]).findings == []
    # outside the serve stack: not gated
    assert lint("rtap_tpu/models/_fx.py", sock,
                ["resource-lifecycle"]).findings == []


def test_lifecycle_join_timeout_none_is_unbounded():
    """join(timeout=None) / join(None) are the UNbounded spellings —
    the keyword's mere presence must not count as bounded."""
    kw_none = _LEAKY_THREAD + (
        "\n    def close(self):\n"
        "        self._t.join(timeout=None)\n")
    r = lint("rtap_tpu/obs/_fx.py", kw_none, ["resource-lifecycle"])
    assert [f.symbol for f in r.findings] == ["R._t:unbounded-join"]
    pos_none = _LEAKY_THREAD + (
        "\n    def close(self):\n"
        "        self._t.join(None)\n")
    r2 = lint("rtap_tpu/obs/_fx.py", pos_none, ["resource-lifecycle"])
    assert [f.symbol for f in r2.findings] == ["R._t:unbounded-join"]


def test_lifecycle_covers_nested_handler_classes():
    """A class nested inside a method (the request-handler idiom) owns
    per-connection resources too — top-level-only scanning would
    exempt exactly the BinaryBatchSource leak class."""
    code = """
import socket

class Outer:
    def build(self):
        class Handler:
            def setup(self):
                self._peer = socket.create_connection(("h", 1))
        return Handler
"""
    r = lint("rtap_tpu/ingest/_fx.py", code, ["resource-lifecycle"])
    assert [f.symbol for f in r.findings] == ["Handler._peer"]


def test_lifecycle_suppression_comment():
    supp = _LEAKY_THREAD.replace(
        "        self._t = threading.Thread(target=self._run, "
        'name="rtap-x",\n',
        "        # rtap: allow[resource-lifecycle] — fixture daemon\n"
        "        self._t = threading.Thread(target=self._run, "
        'name="rtap-x",\n')
    r = lint("rtap_tpu/obs/_fx.py", supp, ["resource-lifecycle"])
    assert r.findings == [] and len(r.suppressed) == 1
