"""Platform selection: which device a JAX-path command runs on, and where
its compile cache lives.

Tests and CPU drives choose the CPU explicitly — ``JAX_PLATFORMS=cpu`` in
the environment (or ``jax.config.update("jax_platforms", "cpu")`` before
first backend use, as tests/conftest.py does), or ``RTAP_FORCE_CPU=1``,
which :func:`maybe_force_cpu` turns into the same config update. A command
asked for the device path that finds no TPU and was NOT told to use the CPU
fails at start (:func:`require_device`): a CPU run must never be mistaken
for a chip run. A chip belongs to one process at a time, so nothing in this
module — or anything a launcher parent imports — may initialize the backend
as an import side effect.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache (cache everything — min
    sizes/times zeroed) and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already points there and no
    directory is set in code; otherwise the fixed ``<repo>/.jax_cache`` (the
    path is part of the cache key, so it never carries a pid, time or temp
    name). serve/replay, the benchmark, the scripts and chip_smoke.py
    all come through here, so they share entries.

    Op metadata is part of the key here (JAX strips it by default): the
    step's `rtap.*` scopes (ops/step.py SCOPES) live in `op_name` only, so
    with stripped keys a cache shared with a commit that names its work
    differently, or not at all, would hand this one that commit's executable
    and a profiler trace would carry the wrong names."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def force_virtual_devices(n: int) -> None:
    """Give this process n virtual CPU devices (must run before first jax
    backend use; afterwards it changes nothing): sets
    --xla_force_host_platform_device_count and pins the CPU platform.
    An existing flag with a DIFFERENT count is an error — silently keeping
    it would make the later mesh construction fail far from the cause."""
    import re

    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    elif int(m.group(1)) < n:
        raise ValueError(
            f"XLA_FLAGS already forces {m.group(1)} host devices but {n} "
            "were requested; unset the flag or raise its value"
        )
    jax.config.update("jax_platforms", "cpu")


def force_cpu_requested(env_var: str = "RTAP_FORCE_CPU") -> bool:
    """One parser for the force-CPU env convention (""/"0" falsy, anything
    else truthy). Artifact writers (e.g. the live-soak `forced_cpu` field)
    must agree with :func:`maybe_force_cpu` about what counts as forced."""
    return os.environ.get(env_var, "") not in ("", "0")


def maybe_force_cpu(env_var: str = "RTAP_FORCE_CPU") -> bool:
    """If ``$RTAP_FORCE_CPU`` is truthy, pin jax to the CPU platform (must be
    called before any jax backend use). Returns whether CPU was forced."""
    if force_cpu_requested(env_var):
        import jax

        jax.config.update("jax_platforms", "cpu")
        return True
    return False


def device_info() -> dict:
    """``{platform, kind, count}`` of the backend as JAX reports it
    (initializes the backend — never call from a launcher parent)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class NoAcceleratorError(RuntimeError):
    """The device path was asked for, JAX found no TPU, and the CPU was not
    chosen explicitly (:func:`require_device`)."""


def require_device() -> dict:
    """The device rule, for every command asked for the device path
    (``--backend tpu`` in serve/replay/eval/nab, the measurement scripts):
    honor ``RTAP_FORCE_CPU``, bring the backend up, and fail unless the
    platform is ``tpu`` or the CPU was chosen explicitly
    (``RTAP_FORCE_CPU=1``, or ``JAX_PLATFORMS``/``jax_platforms`` set to
    ``cpu``). Returns :func:`device_info`."""
    import jax

    maybe_force_cpu()
    info = device_info()
    explicit_cpu = (jax.config.jax_platforms or "").strip().lower() == "cpu"
    if info["platform"] != "tpu" and not explicit_cpu:
        raise NoAcceleratorError(
            f"the device path was asked for but JAX found no TPU (platform "
            f"{info['platform']!r}, {info['count']} x {info['kind']}); "
            "refusing to run on a fallback. For a CPU run say so: "
            "JAX_PLATFORMS=cpu or RTAP_FORCE_CPU=1")
    return info
