"""Test harness config: run all tests on CPU with 8 virtual devices.

The tests validate semantics + sharding on a virtual CPU mesh (SURVEY.md §4
item 6); the chip is reached by sending a command — first of all
`python chip_smoke.py` — through the chip tool, never from pytest.

Why the platform and the device count are set HERE: the device path refuses
to run off a TPU unless the CPU was chosen explicitly
(rtap_tpu.utils.platform.require_device), and this is that choice for every
in-process test — `jax.config.update("jax_platforms", "cpu")` works any time
before first backend use, whatever the environment says (tier-1 also exports
JAX_PLATFORMS=cpu; children get RTAP_FORCE_CPU=1 or inherit it). XLA_FLAGS is
read at backend init, so the 8 virtual devices the mesh tests need
(tests/scale/) can still be requested here, before jax is first used.
"""

import os
import threading
import time

import pytest

_xla = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla:
    os.environ["XLA_FLAGS"] = (_xla + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def _no_leaked_nondaemon_threads():
    """Every test must join its non-daemon threads (ISSUE 2 CI satellite).

    Hung-thread regressions are exactly what chaos/serve runs produce —
    a dispatch pool whose shutdown path was skipped on a fault, a wedged
    producer — and a leaked non-daemon thread hangs the whole pytest
    process at exit, which CI reports as a timeout instead of the guilty
    test. A short grace period lets orderly shutdowns (pool.shutdown,
    server close) finish; daemon threads (listeners, watchers) are
    exempt by construction."""
    before = set(threading.enumerate())
    yield
    deadline = time.time() + 2.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive() and not t.daemon]
        if not leaked:
            return
        if time.time() > deadline:
            raise AssertionError(
                f"test leaked non-daemon thread(s): "
                f"{[t.name for t in leaked]} — these hang pytest at exit "
                "(join them or mark them daemon)")
        time.sleep(0.05)
