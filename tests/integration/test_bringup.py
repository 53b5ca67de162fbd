"""Bring-up contracts (ISSUE 21): the device rule, the placeable compile
cache, imports that claim no chip, content-keyed native artefacts, and
chip_smoke.py rehearsed off the chip at tiny sizes.

All CPU. Subprocess cases run children exactly as a launcher would — the
point of most of them is what a FRESH process does before anything chose a
platform for it.
"""

import ctypes
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# "is a backend initialized?" through the public API only: pointing
# jax_platforms at a platform that does not exist makes jax.devices() raise
# unless the backends were already brought up (then it answers from cache)
_PROBE = """
import jax
jax.config.update("jax_platforms", "no_such_platform")
try:
    jax.devices()
    print("BACKEND_INITIALIZED")
except RuntimeError:
    print("backend_untouched")
"""


def _py(code: str, env_changes: dict | None = None, args: tuple = (),
        cwd: str = REPO) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "RTAP_FORCE_CPU", "XLA_FLAGS")}
    env["PYTHONPATH"] = REPO
    for k, v in (env_changes or {}).items():
        env[k] = v
    return subprocess.run([sys.executable, *(("-c", code) if code else ()),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


# ---------------------------------------------------------- device rule ----


@pytest.mark.parametrize("env,runs", [
    ({}, False),  # no TPU here and nobody chose the CPU: refuse
    ({"RTAP_FORCE_CPU": "1"}, True),
    ({"JAX_PLATFORMS": "cpu"}, True),
], ids=["nobody_chose", "RTAP_FORCE_CPU", "JAX_PLATFORMS_cpu"])
def test_require_device_needs_a_tpu_or_an_explicit_cpu(env, runs):
    proc = _py("import json\n"
               "from rtap_tpu.utils.platform import require_device\n"
               "print(json.dumps(require_device()))", env)
    if runs:
        assert proc.returncode == 0, proc.stderr[-400:]
        info = json.loads(proc.stdout.strip().splitlines()[-1])
        assert info == {"platform": "cpu", "kind": "cpu", "count": 1}
    else:
        assert proc.returncode != 0
        assert "NoAcceleratorError" in proc.stderr and "no TPU" in proc.stderr
        assert proc.stdout.strip() == ""


def test_serve_device_path_without_tpu_exits_before_scoring(tmp_path):
    """`serve --backend tpu` with no TPU and no explicit CPU choice exits
    non-zero at start: no listener, no stats line, no alert file."""
    alerts = tmp_path / "alerts.jsonl"
    proc = _py("", args=("-m", "rtap_tpu", "serve", "--backend", "tpu",
                         "--streams", "a,b", "--ticks", "1", "--cadence",
                         "0.01", "--alerts", str(alerts)))
    assert proc.returncode != 0
    assert "JAX found no TPU" in proc.stderr
    assert "listening" not in proc.stderr
    assert proc.stdout.strip() == "" and not alerts.exists()


def test_device_stats_names_the_platform(monkeypatch):
    """The stats line says where the device groups ran — and nothing at
    all for a pure CPU-oracle run, which must not bring a backend up."""
    import jax

    import rtap_tpu.utils.platform as platform
    from rtap_tpu.service.loop import _device_stats

    class _Grp:
        def __init__(self, backend):
            self.backend = backend

    monkeypatch.setattr(platform, "device_info", lambda: pytest.fail(
        "a cpu-oracle run initialized the backend for its stats line"))
    assert _device_stats([_Grp("cpu")]) == {}
    monkeypatch.undo()
    out = _device_stats([_Grp("cpu"), _Grp("tpu")])
    assert out == {"platform": "cpu", "device_kind": "cpu",
                   "device_count": len(jax.devices())}


def test_device_stats_on_tpu_shows_a_memory_stats_failure(monkeypatch):
    """On a TPU a failing (or empty) memory_stats() is an error the stats
    line shows — not the silent {} it used to be."""
    import jax

    import rtap_tpu.utils.platform as platform
    from rtap_tpu.service.loop import _device_stats

    class _Grp:
        backend = "tpu"

    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            if isinstance(self._stats, Exception):
                raise self._stats
            return self._stats

    monkeypatch.setattr(platform, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev(RuntimeError("stats unavailable"))])
    out = _device_stats([_Grp()])
    assert out["platform"] == "tpu" and out["device_kind"] == "TPU v5 lite"
    assert "stats unavailable" in out["hbm_error"]
    assert "hbm_bytes_in_use" not in out
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(None)])
    assert "no bytes_in_use" in _device_stats([_Grp()])["hbm_error"]
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _Dev({"bytes_in_use": 7, "peak_bytes_in_use": 9})])
    assert _device_stats([_Grp()]) == {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
        "hbm_bytes_in_use": 7, "hbm_peak_bytes_in_use": 9}


# -------------------------------------------------------- compile cache ----


@pytest.mark.parametrize("env_dir", ["/some/where/else", None],
                         ids=["env_set", "env_unset"])
def test_compile_cache_dir_env_wins_else_fixed_in_checkout(monkeypatch, env_dir):
    """Where JAX_COMPILATION_CACHE_DIR is set the program sets no directory
    in code; unset, the fixed <repo>/.jax_cache (never a temp/pid/time)."""
    import jax

    from rtap_tpu.utils.platform import enable_compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == \
            os.path.join(REPO, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert enable_compile_cache() == env_dir
        assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    # the step's scope names live in op metadata: they are part of the key
    assert updates["jax_compilation_cache_include_metadata_in_key"] is True


# ------------------------------------------------- one process per chip ----


@pytest.mark.parametrize("what,code", [
    ("kernels and registry",
     "import rtap_tpu.ops, rtap_tpu.ops.step, rtap_tpu.ops.tm_tpu\n"
     "import rtap_tpu.service.registry, rtap_tpu.service.loop\n"),
    ("the state-bytes gate a launcher runs before its children: the real "
     "arrays' byte sum against scalingmath's static derivation",
     "import numpy as np\n"
     "from rtap_tpu.analysis.scalingmath import derived_stream_bytes\n"
     "from rtap_tpu.config import cluster_preset\n"
     "from rtap_tpu.models.state import init_state\n"
     "st = init_state(cluster_preset(perm_bits=16))\n"
     "measured = sum(int(np.asarray(v).nbytes) for v in st.values())\n"
     "assert measured == derived_stream_bytes('.', 16) > 0, measured\n"),
    ("serve --supervise parent",
     "import rtap_tpu.__main__ as cli\n"
     "import rtap_tpu.resilience.supervisor as sup\n"
     "sup.Supervisor.run = lambda self: 0  # the child is not the point\n"
     "assert cli.main(['serve', '--supervise', '--backend', 'tpu',\n"
     "                 '--streams', 'a', '--checkpoint-dir', 'ck',\n"
     "                 '--journal-dir', 'jr']) == 0\n"),
], ids=["kernels_and_registry", "state_bytes_gate", "serve_supervise_parent"])
def test_launcher_parents_leave_the_backend_uninitialized(what, code):
    """A parent that touched JAX would hold the chip its child needs: the
    imports and launcher paths below must not initialize any backend (no
    platform is chosen for the child process — the chip machine's state)."""
    proc = _py(code + _PROBE)
    assert proc.returncode == 0, proc.stderr[-600:]
    assert proc.stdout.strip().splitlines()[-1] == "backend_untouched", what


# ------------------------------------------------------ native artefact ----


def test_native_artefact_is_keyed_on_source_content(tmp_path, monkeypatch):
    """Only a binary built from the PRESENT .c is ever loaded: a stale .so
    with a NEWER mtime than its source (a copied tree) is not picked up,
    and touching the source alone rebuilds nothing."""
    import rtap_tpu.native as native

    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "_build"))
    src = tmp_path / "answer.c"
    src.write_text("int answer(void) { return 1; }\n")
    so1 = native._built(str(src))
    assert ctypes.CDLL(so1).answer() == 1
    future = os.path.getmtime(so1) + 3600
    os.utime(so1, (future, future))  # the stale binary looks newest of all
    src.write_text("int answer(void) { return 2; }\n")
    so2 = native._built(str(src))
    assert so2 != so1 and ctypes.CDLL(so2).answer() == 2
    os.utime(src, (future + 60, future + 60))  # touch: same content
    stamp = os.path.getmtime(so2)
    assert native._built(str(src)) == so2 and os.path.getmtime(so2) == stamp


# ------------------------------------------------ chip_smoke rehearsals ----


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    """chip_smoke as a module plus a rehearsal size table: tiny sizes, the
    CPU chosen explicitly, one compile cache shared by every rehearsal —
    placed from outside, which is the env-var contract end to end."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    cache = tmp_path_factory.mktemp("jax_cache")
    sizes = dict(chip_smoke.SIZES, rehearsal=True, score_streams=8,
                 score_chunk=4, oracle_streams=4,
                 serve_streams=16, serve_group=8,
                 serve_ticks=4, cadence_s=0.5, mesh_streams=8, mesh_chunk=3)
    env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(cache),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    yield chip_smoke, sizes, cache
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def test_chip_smoke_default_run_rehearsed_on_cpu(smoke, tmp_path, capfd):
    """Both phases' control flow runs end to end (every check of both
    passes) — and the verdict is still false with a non-zero exit, because
    the platform is not tpu. The cache landed where the env var said."""
    chip_smoke, sizes, cache = smoke
    assert chip_smoke.run(1, sizes, str(tmp_path / "out")) != 0
    out = capfd.readouterr().out
    assert _last_json(out) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    for phase in ("score", "serve"):
        line = next(ln for ln in out.splitlines()
                    if ln.startswith(f"[{phase}] checks "))
        checks = json.loads(line.split(" checks ", 1)[1])
        assert checks and all(checks.values()), (phase, checks)
    assert "[mesh]" not in out
    assert os.listdir(cache), "children ignored JAX_COMPILATION_CACHE_DIR"


def test_chip_smoke_fails_on_a_quarantined_group_though_serve_exits_0(
        smoke, tmp_path):
    """serve is crash-isolated: a group whose dispatch fails is quarantined
    and the run still exits 0. The smoke reads the events, not the code."""
    chip_smoke, sizes, _ = smoke
    spec = tmp_path / "chaos.json"
    spec.write_text(json.dumps({"seed": 1, "faults": [
        {"kind": "dispatch_exception", "tick": 1, "group": 1}]}))
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    res = chip_smoke.serve_phase(sizes, str(out_dir),
                                 extra_args=("--chaos-spec", str(spec)))
    assert res["checks"]["exit_zero"] is True
    assert res["checks"]["no_failing_events"] is False
    assert res["ok"] is False


def test_chip_smoke_chips_4_runs_only_the_mesh_phase(smoke, tmp_path, capfd):
    """--chips 4 on four virtual devices: shards on four distinct devices,
    no collective, equal to the one-chip control — and no default phase."""
    chip_smoke, sizes, _ = smoke
    assert chip_smoke.run(4, sizes, str(tmp_path / "out")) != 0
    out = capfd.readouterr().out
    assert _last_json(out) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    checks = json.loads(next(
        ln for ln in out.splitlines()
        if ln.startswith("[mesh] checks ")).split(" checks ", 1)[1])
    assert checks == {"four_devices": True, "shards_placed": True,
                      "shards_stay_placed": True, "collective_free": True,
                      "scores_finite": True, "matches_one_chip": True}
    assert "[score]" not in out and "[serve]" not in out


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    the script exits non-zero and its last line says ok: false."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert _last_json(proc.stdout) == {"ok": False, "device": {}}
