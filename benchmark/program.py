"""The system under test, as the benchmark reaches it.

The only module of the benchmark that imports ``rtap_tpu``: it builds the
program's own objects (StreamGroup, StreamGroupRegistry, TcpJsonlSource,
live_loop, TraceRecorder) from a benchmark configuration and hands them to
the traffic kinds. Nothing here computes a metric or decides `correct`."""

from __future__ import annotations

import copy
import sys
import time

from benchmark.feed import stream_ids


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for: exit code 3, no result."""


def require_chip(chips: int, allow_cpu: bool = False) -> dict:
    """Bring the backend up -> {platform, kind, count}. Off a TPU (or on
    fewer chips than `chips`) the run ends here, non-zero, with no result
    line; `allow_cpu` is the tests' CPU rehearsal and nothing else."""
    try:
        from rtap_tpu.utils.platform import (
            NoAcceleratorError, enable_compile_cache, require_device)
    except ImportError as e:
        print(f"benchmark: the rtap_tpu package is not importable: {e}",
              file=sys.stderr)
        raise NoChip(3) from e
    try:
        device = require_device()
    except NoAcceleratorError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        raise NoChip(3) from e
    if not allow_cpu and (device["platform"] != "tpu"
                          or device["count"] < chips):
        print(f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
              f"{device}", file=sys.stderr)
        raise NoChip(3)
    # JAX_COMPILATION_CACHE_DIR if set, else the fixed <checkout>/.jax_cache
    cache_dir = enable_compile_cache()
    return {**device, "compile_cache": cache_dir}


def model_config(config: dict, control: bool = False):
    """The program's ModelConfig of a benchmark configuration file; with
    `control`, the configuration's lower-precision path switched on."""
    from rtap_tpu.config import ModelConfig

    model = copy.deepcopy(config["model"])
    if control:
        for section, keys in config["control"]["model_overrides"].items():
            model[section].update(keys)
    return ModelConfig.from_dict(model)


def build_groups(cfg, n_groups: int, group_size: int, seed: int) -> list:
    """`n_groups` StreamGroups with their state on the device, group g's
    made from `seed + g` as the registry does (one host init, one on-chip
    broadcast per group)."""
    from rtap_tpu.service.registry import StreamGroup

    ids = stream_ids(n_groups * group_size)
    return [StreamGroup(cfg, ids[g * group_size:(g + 1) * group_size],
                        seed=seed + g, backend="tpu") for g in range(n_groups)]


def build_registry(cfg, n_groups: int, group_size: int, seed: int):
    """A finalized StreamGroupRegistry of `n_groups` full groups, as serve
    builds it -> (registry, ids in dispatch order)."""
    from rtap_tpu.service.registry import StreamGroupRegistry

    reg = StreamGroupRegistry(cfg, group_size=group_size, backend="tpu",
                              seed=seed)
    for sid in stream_ids(n_groups * group_size):
        reg.add_stream(sid)
    reg.finalize()
    if len(reg.groups) != n_groups:
        raise RuntimeError(f"registry built {len(reg.groups)} groups, "
                           f"configuration says {n_groups}")
    return reg, reg.dispatch_ids()


def tcp_source(ids: list[str], require_native: bool = True):
    """serve's TCP JSONL listener on a free localhost port, started."""
    from rtap_tpu.service.sources import TcpJsonlSource

    return TcpJsonlSource(ids, port=0,
                          native=True if require_native else None).start()


def trace_recorder():
    from rtap_tpu.obs import TraceRecorder

    return TraceRecorder(capacity=1 << 18, process_name="benchmark-live")


def live_loop(source, registry, n_ticks: int, cadence_s: float, traffic: dict,
              trace, stop_event) -> dict:
    """serve's loop with the traffic mix's flags -> its stats dict."""
    from rtap_tpu.service.loop import live_loop as loop

    return loop(source, registry, n_ticks=n_ticks, cadence_s=cadence_s,
                pipeline_depth=traffic["pipeline_depth"],
                micro_chunk=traffic["micro_chunk"], learn=traffic["learn"],
                aot_warmup=True, trace=trace, stop_event=stop_event)


def wait_device(handle: dict) -> None:
    """Block until a dispatched chunk's scores exist on the device (the
    wait that collect_chunk's fetch would otherwise hide inside itself)."""
    import jax

    jax.block_until_ready(handle["out"])


def state_rows(group, slot: int, leaves: tuple[str, ...]) -> dict:
    """One stream's rows of the named state leaves, on the host."""
    import numpy as np

    return {k: np.asarray(group.state[k][slot]) for k in leaves}


def overflow_total(groups) -> int:
    import numpy as np

    return int(sum(int(np.asarray(g.state["tm_overflow"]).sum())
                   for g in groups))


def capacity_total(groups) -> dict:
    """Every group's `StreamGroup.capacity_stats()` as one count over all
    resident streams (full cells and columns add, the high-water mark is the
    highest); {} for a program that has no such counter."""
    totals: dict = {}
    for g in groups:
        if not hasattr(g, "capacity_stats"):
            return {}
        for k, v in g.capacity_stats().items():
            totals[k] = (max(totals.get(k, 0), int(v)) if k.startswith("max_")
                         else totals.get(k, 0) + int(v))
    return totals


def memory_peak_bytes() -> int:
    """peak_bytes_in_use of the fullest device (0 where the backend reports
    no memory stats — the CPU rehearsal)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class CompileCounter:
    """Counts backend compilations (JAX's own monitoring event) between
    `start()` and now: inside a measured window there must be none."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event == self._EVENT:
            self.count += 1

    def start(self) -> None:
        self.armed = True

    def stop(self) -> int:
        self.armed = False
        return self.count


def profiler_start(log_dir: str) -> float:
    """Start the JAX profiler (device + light host tracing, no Python
    tracer) and drop a sync annotation -> the perf_counter reading that the
    annotation's start stands for on the trace's own timeline."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_sync"):
        time.sleep(0.001)
    return t


def profiler_stop() -> None:
    import jax

    jax.profiler.stop_trace()
