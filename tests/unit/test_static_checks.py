"""scripts/check_static.sh rides tier-1: compileall over rtap_tpu AND
scripts/, plus `python -m rtap_tpu.analysis` (rtap-lint,
ISSUE 12) — the AST invariant analyzer that now owns the print gate
(NO print() in the serve stack; elsewhere print() must target an
explicit stream or be the one-JSON-line artifact emission), the
MUST_BE_STRICT coverage pin, and the race/purity/exception/flag-docs
passes. The gate is zero unsuppressed findings against the committed
analysis_baseline.json.

Also gated here (ISSUE 12 CI satellite): the analyzer's CPU budget —
it must never become the slow part of the static gate on the 1-core
tier-1 host — and the --json artifact contract soaks/hw_session
archive."""

import glob
import json
import os
import resource
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the analyzer's CPU budget on the 1-core tier-1 host (ISSUE 12: the
#: static gate must stay fast; measured ~1.6 s — the 10 s ceiling is
#: headroom, not a target). Budgets here are CHILD CPU SECONDS, not
#: wall time: wall budgets flaked whenever a concurrent process stole
#: the host mid-run (a 5 s analysis read as 13+ s under suite load) —
#: CPU time pins the analyzer's WORK, which is what the budget is
#: about, and is immune to preemption (the paced-loop deflake pattern:
#: pin semantics, not speed).
ANALYZER_BUDGET_S = 10.0


def _child_cpu_s():
    r = resource.getrusage(resource.RUSAGE_CHILDREN)
    return r.ru_utime + r.ru_stime


def _run():
    return subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "check_static.sh")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )


def _cleanup(victim, subdir):
    os.remove(victim)
    # the script's compileall step byte-compiles the canary before the
    # analyzer fails — drop the orphaned pyc too, not just the source
    base = os.path.splitext(os.path.basename(victim))[0]
    for pyc in glob.glob(os.path.join(subdir, "__pycache__", base + "*")):
        os.remove(pyc)


def test_check_static_passes():
    proc = _run()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "check_static: OK" in proc.stdout


def test_print_gate_bites_in_serve_stack():
    """The strict gate must fail on ANY print( in service/ — even one
    aimed at stderr (guard the guard: a checker regression could silently
    let prints back into the serve stack)."""
    subdir = os.path.join(REPO, "rtap_tpu", "service")
    victim = os.path.join(subdir, "_gate_canary.py")
    with open(victim, "w") as f:
        f.write('import sys\nprint("scraped", file=sys.stderr)\n')
    try:
        proc = _run()
    finally:
        _cleanup(victim, subdir)
    assert proc.returncode != 0
    assert "_gate_canary" in proc.stdout + proc.stderr


def test_print_gate_not_suppressible():
    """print-strict is gate-critical plumbing: an inline allow comment
    must NOT silence it (a suppressible guard is no guard)."""
    subdir = os.path.join(REPO, "rtap_tpu", "obs")
    victim = os.path.join(subdir, "_gate_canary_ns.py")
    with open(victim, "w") as f:
        f.write('import sys\n'
                'print("x", file=sys.stderr)  # rtap: allow[print-strict]\n')
    try:
        proc = _run()
    finally:
        _cleanup(victim, subdir)
    assert proc.returncode != 0
    assert "_gate_canary_ns" in proc.stdout + proc.stderr


def test_print_gate_bites_in_obs():
    """The strict gate covers rtap_tpu/obs/ too — the tracing/flight
    modules (ISSUE 4) live there, and a postmortem path that printed to
    stdout would corrupt the one-JSON-line serve artifact contract."""
    subdir = os.path.join(REPO, "rtap_tpu", "obs")
    victim = os.path.join(subdir, "_gate_canary_o.py")
    with open(victim, "w") as f:
        f.write('import sys\nprint("trace", file=sys.stderr)\n')
    try:
        proc = _run()
    finally:
        _cleanup(victim, subdir)
    assert proc.returncode != 0
    assert "_gate_canary_o" in proc.stdout + proc.stderr


def test_print_gate_bites_in_scripts():
    """The widened gate (ISSUE 3 satellite) must catch a bare print in
    scripts/ — including the multi-line call form a line-grep cannot see —
    while leaving file=stderr diagnostics and JSON emission legal."""
    subdir = os.path.join(REPO, "scripts")
    victim = os.path.join(subdir, "_gate_canary_s.py")
    with open(victim, "w") as f:
        f.write('print(\n    "bare stdout"\n)\n')
    try:
        proc = _run()
    finally:
        _cleanup(victim, subdir)
    assert proc.returncode != 0
    assert "_gate_canary_s" in proc.stdout + proc.stderr


def test_analyzer_budget_and_json_artifact():
    """One invocation, two gates: a COLD `python -m rtap_tpu.analysis
    --json --no-cache` (all twenty passes live, no cache shortcut) must
    finish inside ANALYZER_BUDGET_S on this 1-core host AND emit exactly
    one parseable JSON artifact line on stdout (the soak/hw_session
    archival surface), reporting ok=true with zero findings against the
    committed baseline."""
    cpu0 = _child_cpu_s()
    proc = subprocess.run(
        [sys.executable, "-m", "rtap_tpu.analysis", "--json",
         "--no-cache"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    cpu = _child_cpu_s() - cpu0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert cpu < ANALYZER_BUDGET_S, (
        f"analyzer burned {cpu:.1f} CPU s (> {ANALYZER_BUDGET_S}s "
        "budget) — it must never become the slow part of the static "
        "gate")
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"--json must emit ONE stdout line, got: {lines}"
    art = json.loads(lines[0])["analysis"]
    assert art["schema_version"] == 4
    assert art["ok"] is True
    assert art["cache"] == "off"
    assert art["findings"] == []
    assert art["files_scanned"] > 50
    assert art["baseline_errors"] == []
    # all twenty passes ran (the per-pass tally is the liveness proof)
    assert set(art["per_pass"]) == {
        "prints", "excepts", "flags", "purity", "races",
        "replay-determinism", "resource-lifecycle", "lock-order",
        "cross-share",
        "trace-safety", "static-hash", "dtype-domain",
        "twin-parity", "donation", "wire-contract",
        "device-scope", "collective-discipline", "shard-resource",
        "partition-contract", "scaling-math"}
    # every committed baseline entry must still match a real finding —
    # stale entries mean the code moved on and the baseline should shrink
    assert art["stale_baseline"] == [], (
        "stale baseline entries — delete them from analysis_baseline.json: "
        f"{art['stale_baseline']}")


def _analysis_json(*extra_args):
    """Run the analyzer; returns (proc, artifact, child CPU seconds).
    CPU seconds — not the artifact's wall-clock elapsed_s — feed the
    budget assertions (see ANALYZER_BUDGET_S: pin work, not speed)."""
    cpu0 = _child_cpu_s()
    proc = subprocess.run(
        [sys.executable, "-m", "rtap_tpu.analysis", "--json", *extra_args],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    cpu = _child_cpu_s() - cpu0
    art = json.loads(proc.stdout.splitlines()[-1])["analysis"]
    return proc, art, cpu


def test_findings_cache_cold_vs_hit_identical_and_subsecond(tmp_path):
    """The ISSUE 13 cache contract, end to end: a cold run and the
    cache-hit run that follows must be FINDING-IDENTICAL (same artifact
    minus timing/cache-mode), and the hit must be sub-second — the
    whole point of hashing instead of re-parsing ~100 files."""
    cache = str(tmp_path / "lint_cache.json")
    _p1, art1, _cpu1 = _analysis_json("--cache-path", cache)
    _p2, art2, cpu2 = _analysis_json("--cache-path", cache)
    assert art1["cache"] == "cold"
    assert art2["cache"] == "hit"
    assert cpu2 < 1.0, (
        f"cache hit burned {cpu2:.2f} CPU s — the incremental path "
        "must stay sub-second")
    for volatile in ("elapsed_s", "cache"):
        art1.pop(volatile), art2.pop(volatile)
    assert art1 == art2, "cached run diverged from the cold run"


def test_findings_cache_invalidated_by_file_edit(tmp_path):
    """Stale-cache invalidation under the PASS-PARTITIONED cache
    (ISSUE 14): after a warm cache, ADDING a file with a violation must
    produce a re-run ("warm" — unchanged files replay their per-file
    pass findings, the new file and every whole-program pass run live)
    that REPORTS the violation — a cache that kept serving the old
    report would be a hole in the gate."""
    cache = str(tmp_path / "lint_cache.json")
    _analysis_json("--cache-path", cache)          # warm it
    subdir = os.path.join(REPO, "rtap_tpu", "obs")
    victim = os.path.join(subdir, "_gate_canary_cache.py")
    with open(victim, "w") as f:
        f.write('import sys\nprint("x", file=sys.stderr)\n')
    try:
        proc, art, _cpu = _analysis_json("--cache-path", cache)
    finally:
        _cleanup(victim, subdir)
    assert proc.returncode != 0
    assert art["cache"] == "warm"
    assert any(f["path"].endswith("_gate_canary_cache.py")
               for f in art["findings"])
    # ... and reverting the edit re-runs again (file-set hash): the
    # next run is live and green, not a stale red replay
    proc3, art3, _cpu3 = _analysis_json("--cache-path", cache)
    assert proc3.returncode == 0 and art3["cache"] == "warm"
    # EDITING an existing file (content change, same file set) must
    # also re-run — the per-file content hash, not the path list, is
    # the freshness judge
    target = os.path.join(REPO, "rtap_tpu", "utils", "measure.py")
    with open(target, encoding="utf-8") as f:
        original = f.read()
    with open(target, "a", encoding="utf-8") as f:
        f.write("\n# cache-invalidation canary (comment only)\n")
    try:
        _proc4, art4, _cpu4 = _analysis_json("--cache-path", cache)
    finally:
        with open(target, "w", encoding="utf-8") as f:
            f.write(original)
    assert art4["cache"] == "warm"


def test_findings_cache_warm_equals_cold_and_meets_budget(tmp_path):
    """The ISSUE 14 pass-partition contract, end to end: a one-file
    edit after a warm cache must (a) produce the same findings picture
    as a from-scratch cold run of the same tree, and (b) cost less CPU
    than that cold run beside it — the point of partitioning with
    twenty passes live. The bar is the cold run's own time on the same
    host in the same minute, not an absolute: under six test workers on
    a shared host the warm run's ~2.2 CPU s read past a 3.0 s budget
    with nothing wrong (ISSUE 50), and cold rises with it."""
    cache = str(tmp_path / "lint_cache.json")
    _analysis_json("--cache-path", cache)          # prime
    target = os.path.join(REPO, "rtap_tpu", "utils", "measure.py")
    with open(target, encoding="utf-8") as f:
        original = f.read()
    with open(target, "a", encoding="utf-8") as f:
        f.write("\n# warm-budget canary (comment only)\n")
    try:
        _p, warm, warm_cpu = _analysis_json("--cache-path", cache)
        _p2, cold, cold_cpu = _analysis_json("--no-cache")
    finally:
        with open(target, "w", encoding="utf-8") as f:
            f.write(original)
    assert warm["cache"] == "warm"
    # the whole-program passes (the mesh model, partition-contract,
    # scaling-math: cross-file inputs) re-run warm; the per-file passes of
    # every unchanged file replay — about 2.2 of 3.2 CPU s on a quiet host
    assert warm_cpu < cold_cpu, (
        f"warm run burned {warm_cpu:.2f} CPU s against {cold_cpu:.2f} cold "
        "— per-file pass reuse must keep incremental runs cheaper than "
        "from scratch")
    for volatile in ("elapsed_s", "cache"):
        warm.pop(volatile), cold.pop(volatile)
    assert warm == cold, "warm partial-reuse run diverged from cold"


def test_sarif_artifact_shape(tmp_path):
    """--sarif writes a SARIF 2.1.0 log beside the one-line --json
    contract: version/schema pinned, every rule listed, results carry
    a physical location and the stable (rule,path,symbol) fingerprint,
    suppressed/baselined findings ride along as suppressions."""
    out = tmp_path / "lint.sarif"
    proc = subprocess.run(
        [sys.executable, "-m", "rtap_tpu.analysis", "--json",
         "--no-cache", "--sarif", str(out)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # stdout still exactly one line — SARIF must not leak onto it
    assert len([ln for ln in proc.stdout.splitlines() if ln.strip()]) == 1
    sarif = json.loads(out.read_text())
    assert sarif["version"] == "2.1.0"
    assert sarif["$schema"].endswith("sarif-2.1.0.json")
    run = sarif["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "rtap-lint"
    rule_ids = {r["id"] for r in driver["rules"]}
    # the rules section is generated from ALL_RULES, so new passes are
    # covered automatically — the v3 ids prove it
    for rid in ("race", "lock-order", "cross-share",
                "replay-determinism", "resource-lifecycle",
                "print-strict", "parse-error",
                "twin-parity", "trace-safety", "donate-read",
                "static-hash", "jit-churn", "dtype-domain",
                "wire-contract"):
        assert rid in rule_ids
    assert run["results"], "green tree still carries suppressed/baselined"
    for res in run["results"]:
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"]
        assert loc["region"]["startLine"] >= 1
        assert "rtapLintKey/v1" in res["partialFingerprints"]
    # the gate is green, so every result must be a suppression carrier
    assert all("suppressions" in r for r in run["results"])


def _canary_bites(subdir_parts, name, code, expect):
    """Drop a violating file into the tree, assert the gate goes red
    naming it — per-pass end-to-end canaries (the fixture tests prove
    the library; these prove the gate stays ARMED). Invokes the
    analyzer directly (its exit code IS the gate check_static.sh
    wraps) to keep the canary fleet inside the tier-1 time budget."""
    subdir = os.path.join(REPO, *subdir_parts)
    victim = os.path.join(subdir, name)
    with open(victim, "w") as f:
        f.write(code)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "rtap_tpu.analysis", "--no-cache"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
    finally:
        _cleanup(victim, subdir)
    assert proc.returncode != 0
    assert expect in proc.stdout + proc.stderr


def test_lock_order_canary_bites_end_to_end():
    _canary_bites(
        ("rtap_tpu", "resilience"), "_gate_canary_lo.py",
        "import threading\n\n\n"
        "class Knot:\n"
        "    def __init__(self):\n"
        "        self._a_lock = threading.Lock()\n"
        "        self._b_lock = threading.Lock()\n\n"
        "    def one(self):\n"
        "        with self._a_lock:\n"
        "            with self._b_lock:\n"
        "                pass\n\n"
        "    def two(self):\n"
        "        with self._b_lock:\n"
        "            with self._a_lock:\n"
        "                pass\n",
        "Knot._a_lock->Knot._b_lock->Knot._a_lock")


def test_cross_share_canary_bites_end_to_end():
    _canary_bites(
        ("rtap_tpu", "service"), "_gate_canary_cs.py",
        "import threading\n\n\n"
        "class CanaryTracker:\n"
        "    def __init__(self):\n"
        "        self.hits = 0\n\n"
        "    def fold(self):\n"
        "        self.hits += 1\n\n"
        "    def snapshot(self):\n"
        "        return self.hits\n\n\n"
        "class CanaryRunner:\n"
        "    def __init__(self, tracker):\n"
        "        self.tracker = tracker\n\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._run, name='rtap-cs',\n"
        "                         daemon=True).start()\n\n"
        "    def _run(self):\n"
        "        pass\n\n\n"
        "def wire(consume):\n"
        "    t = CanaryTracker()\n"
        "    r = CanaryRunner(t)\n"
        "    consume(t)\n"
        "    return r\n",
        "CanaryTracker.hits")


def test_replay_determinism_canary_bites_end_to_end():
    _canary_bites(
        ("rtap_tpu", "correlate"), "_gate_canary_rd.py",
        "def emit(fh):\n"
        "    acc = set()\n"
        "    acc.add('x')\n"
        "    for item in acc:\n"
        "        fh.write(item)\n",
        "emit:set-iter")


def test_resource_lifecycle_canary_bites_end_to_end():
    _canary_bites(
        ("rtap_tpu", "obs"), "_gate_canary_rl.py",
        "import threading\n\n\n"
        "class Leaky:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run,\n"
        "                                   name='rtap-rl', daemon=True)\n"
        "        self._t.start()\n\n"
        "    def _run(self):\n"
        "        pass\n",
        "Leaky._t")


def test_race_canary_bites_end_to_end():
    """A deliberately racy class dropped into the serve stack must fail
    the whole gate (the ISSUE 12 acceptance shape: the analyzer, not a
    reviewer, catches the next Lease.set_meta-class bug)."""
    subdir = os.path.join(REPO, "rtap_tpu", "resilience")
    victim = os.path.join(subdir, "_gate_canary_r.py")
    with open(victim, "w") as f:
        f.write(
            "import threading\n\n\n"
            "class Racy:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "        self._lock = threading.Lock()\n\n"
            "    def start(self):\n"
            "        t = threading.Thread(target=self._run,\n"
            "                             name='rtap-canary-r', daemon=True)\n"
            "        t.start()\n\n"
            "    def _run(self):\n"
            "        self.n += 1\n\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self.n += 1\n"
        )
    try:
        proc = _run()
    finally:
        _cleanup(victim, subdir)
    assert proc.returncode != 0
    assert "Racy.n" in proc.stdout + proc.stderr


# ---- ISSUE 14: the device-kernel pass family stays ARMED end to end ----

def test_twin_parity_canary_bites_end_to_end():
    """An untwinned public kernel dropped into ops/ fails the gate —
    the acceptance shape: removing a kernel's oracle (or its parity
    test) is an analyzer failure, not a review catch."""
    _canary_bites(
        ("rtap_tpu", "ops"), "_gate_canary_tp.py",
        "import jax.numpy as jnp\n\n\n"
        "def phantom_kernel(x):\n"
        "    return jnp.sum(x)\n",
        "phantom_kernel:untwinned")


def test_traced_if_canary_bites_end_to_end():
    """The traced-`if` canary (ISSUE 14 satellite): data-dependent
    Python control flow in a kernel fails the gate."""
    _canary_bites(
        ("rtap_tpu", "ops"), "_gate_canary_ts.py",
        "import jax.numpy as jnp\n\n\n"
        "def leaky_kernel(x: jnp.ndarray):\n"
        "    y = jnp.sum(x)\n"
        "    if y > 0:\n"
        "        return y\n"
        "    return -y\n",
        "leaky_kernel:if-on-traced:y")


def test_donated_read_canary_bites_end_to_end():
    """The donated-read canary (ISSUE 14 satellite): reading a buffer
    after donating it to a jit wrapper — garbage on TPU, invisible to
    CPU tier-1 — fails the gate."""
    _canary_bites(
        ("rtap_tpu", "service"), "_gate_canary_dr.py",
        "from functools import partial\n\nimport jax\n\n\n"
        "@partial(jax.jit, donate_argnums=(0,))\n"
        "def _canary_burn(state):\n"
        "    return state\n\n\n"
        "def leak(state):\n"
        "    out = _canary_burn(state)\n"
        "    return state, out\n",
        "leak:state@_canary_burn")


def test_jit_churn_canary_bites_end_to_end():
    _canary_bites(
        ("scripts",), "_gate_canary_sh.py",
        "import jax\n\n\n"
        "def churn(fns):\n"
        "    for fn in fns:\n"
        "        g = jax.jit(fn)\n"
        "    return g\n",
        "churn:jit-loop")


def test_dtype_domain_canary_bites_end_to_end():
    _canary_bites(
        ("rtap_tpu", "ops"), "_gate_canary_dd.py",
        "# rtap: domain[pa=u8, pb=u16]\n"
        "import jax.numpy as jnp\n\n\n"
        "def mixer(pa, pb):\n"
        "    return jnp.sum(pa + pb)\n",
        "mixer:mix:u16~u8")


def test_wire_contract_canary_bites_end_to_end():
    """A second framing reusing the journal's RJ magic (and narrowing
    its documented len field) must fail against the REAL docs — the
    seeded-drift acceptance criterion."""
    _canary_bites(
        ("rtap_tpu", "resilience"), "_gate_canary_wc.py",
        "import struct\n\n"
        "_MAGIC = b\"RJ\"\n"
        "_HEADER = struct.Struct(\"<2sBH\")  # magic, type, len\n",
        "magic:RJ")


# ---- ISSUE 15: the mesh-readiness pass family stays ARMED end to end ----

def test_collective_in_scan_canary_bites_end_to_end():
    """The seeded collective-in-scan canary (ISSUE 15 acceptance): a
    psum inside a chunk-scan body dropped into ops/ fails the gate —
    sharded_chunk_step's collective-free property is a permanent gate,
    not an inspection result."""
    _canary_bites(
        ("rtap_tpu", "ops"), "_gate_canary_cd.py",
        "import jax\nimport jax.numpy as jnp\n\n\n"
        "def sneaky_chunk(state, values):\n"
        "    def body(s, v):\n"
        "        coupled = jax.lax.psum(v, axis_name='streams')\n"
        "        return s, coupled\n"
        "    return jax.lax.scan(body, state, values)\n",
        "collective:psum")


def test_unannotated_leaf_canary_bites_end_to_end():
    """The unannotated-leaf canary (ISSUE 15 acceptance): a new state
    tree in models/ whose leaves carry no partition rules fails the
    gate — a brand-new subsystem cannot dodge the contract by not
    opting in (constructor discovery is structural)."""
    _canary_bites(
        ("rtap_tpu", "models"), "_gate_canary_pc.py",
        "import numpy as np\n\n\n"
        "def init_canary_tree(n):\n"
        "    return {\n"
        "        'canary_a': np.zeros(n, np.float32),\n"
        "        'canary_b': np.zeros(n, np.int32),\n"
        "        'canary_c': np.zeros(n, bool),\n"
        "    }\n",
        "init_canary_tree:unruled:canary_a")


def test_shard_resource_mint_canary_bites_end_to_end():
    """A serve-stack file minting a sidecar path by bare concat fails
    the gate — only service/shardpath.py may spell the suffixes, so a
    new call site cannot forget the shard."""
    _canary_bites(
        ("rtap_tpu", "service"), "_gate_canary_sr.py",
        "def sidecar_for(alert_path):\n"
        "    return alert_path + '.corr'\n",
        "sidecar_for:mint")


def test_device_scope_canary_bites_end_to_end():
    """A devices()[0] read dropped into the serve stack fails the gate
    (the loop.py:_occupancy class this PR fixed, pinned armed)."""
    _canary_bites(
        ("rtap_tpu", "obs"), "_gate_canary_ds.py",
        "def probe():\n"
        "    import jax\n\n"
        "    return jax.local_devices()[0].memory_stats()\n",
        "probe:device0")


def test_scaling_math_canary_bites_end_to_end():
    """Staling SCALING.md's analytic table (a config edit without a
    scaling_law.py re-run) fails the gate: the doc's memory twin. The
    canary perturbs ONE digit of the committed bytes/stream table in
    place and restores it byte-exactly."""
    import re

    scaling = os.path.join(REPO, "SCALING.md")
    with open(scaling, encoding="utf-8") as f:
        original = f.read()
    doctored, n = re.subn(r"\| u16 quanta \| 302,101 \|",
                          "| u16 quanta | 302,102 |", original, count=1)
    assert n == 1, "SCALING.md analytic table row moved — update canary"
    with open(scaling, "w", encoding="utf-8") as f:
        f.write(doctored)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "rtap_tpu.analysis", "--no-cache"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
    finally:
        with open(scaling, "w", encoding="utf-8") as f:
            f.write(original)
    assert proc.returncode != 0
    assert "bytes:u16" in proc.stdout + proc.stderr
