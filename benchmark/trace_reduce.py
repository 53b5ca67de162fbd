"""From a profiler trace to numbers: device busy/idle, per-op time, the
program executions, and idle gaps attributed to what the host was doing.

Two steps, so the arithmetic can be checked without a chip: `load_xplane`
turns the profiler's .xplane.pb into a plain event list
({plane: {line: [[name, start_ns, dur_ns], ...]}}; the host plane's
`bench_*` annotations gathered under the line `annotations`), and `reduce` works on
that list alone (tests/benchmark/test_trace_reduce.py runs it on a reduced
recording of a real trace, benchmark/fixtures/trace_v5e_chunk_step.json).

What a TPU trace looks like (JAX 0.9, TPU v5 lite): plane `/device:TPU:<n>`
has the lines `XLA Modules` (one event per program execution, named
`jit_<fn>(<hash>)`) and `XLA Ops` (one event per HLO op, named by its HLO
text `%<op> = <type> ...`); plane `/host:CPU` has one line per thread, the
main thread's line carrying `jax.profiler.TraceAnnotation`s. All share one
timeline in nanoseconds."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SYNC_NAME = "bench_sync"


def load_xplane(log_dir: str) -> dict:
    """The newest trace under a `jax.profiler.start_trace(log_dir)` directory
    -> event list (device planes' module and op lines, the host's python
    line)."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes: dict = {}
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if device and line.name in ("XLA Modules", "XLA Ops"):
                planes.setdefault(plane.name, {})[line.name] = [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events]
            elif not device:
                # host threads are named after the process; only the
                # benchmark's own annotations are wanted from them
                planes.setdefault(plane.name, {}).setdefault(
                    "annotations", []).extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith("bench_"))
    return planes


def op_label(hlo_text: str) -> str:
    """`%fusion.2 = pred[1024,256]{1,0:T(8,128)} fusion(...)` ->
    `fusion.2:pred[1024,256]`: the op's own name and result type, without
    layout or operands, short enough for a ledger line."""
    m = re.match(r"%?([^\s=]+)\s*=\s*\(?([A-Za-z0-9_]+\[[^\]]*\])?", hlo_text)
    if not m:
        return hlo_text[:64]
    return (m.group(1) + (":" + m.group(2) if m.group(2) else ""))[:64]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _self_times(events: list) -> list[tuple[str, int]]:
    """(name, self ns) per event of one line: its duration minus the events
    nested inside it (a `while` op spans its whole loop body; the time
    belongs to the ops of the body)."""
    out: list[list] = []
    stack: list[tuple[int, int]] = []  # (end_ns, index into out)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append((start + dur, len(out) - 1))
    return [(n, max(0, d)) for n, d in out]


def reduce(planes: dict, window_ns: tuple[int, int],
           host_spans_ns: list[tuple[str, int, int]] = (), top: int = 10) -> dict:
    """-> {busy_s (mean over device planes), window_s, per_device_busy_s,
    device_ops [[label, self seconds], ...] (top `top`, summed over devices),
    modules {name: {count, seconds}}, idle_gaps [[span name, seconds], ...]}.

    `window_ns` clips everything to the traced window (trace timeline);
    `host_spans_ns` are (name, start, end) on the same timeline — a gap on
    the first device is attributed to the spans that cover it, the shortest
    covering span first, and what no span covers to "unattributed"."""
    w0, w1 = window_ns
    if w1 <= w0:
        raise ValueError("empty trace window")
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    per_device, ops, modules = [], {}, {}
    first_busy: list[tuple[int, int]] = []
    for d in devices:
        lines = planes[d]
        op_events = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        clipped = [(max(s, w0), min(s + dur, w1)) for _n, s, dur in op_events
                   if s + dur > w0 and s < w1]
        busy = _union(clipped)
        per_device.append(sum(b - a for a, b in busy) / 1e9)
        if d == devices[0]:
            first_busy = busy
        for name, self_ns in _self_times(
                [e for e in lines.get("XLA Ops", [])
                 if e[1] + e[2] > w0 and e[1] < w1]):
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + self_ns / 1e9
        for name, s, dur in lines.get("XLA Modules", []):
            if s >= w0 and s + dur <= w1:  # whole executions only
                m = modules.setdefault(re.sub(r"\(\d+\)$", "", name),
                                       {"count": 0, "seconds": 0.0})
                m["count"] += 1
                m["seconds"] += dur / 1e9
    # idle gaps of the first device, by what the host was doing
    gaps, cursor = [], w0
    for a, b in first_busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < w1:
        gaps.append((cursor, w1))
    spans = sorted(((e - s, name, s, e) for name, s, e in host_spans_ns
                    if e > s), key=lambda x: x[0])
    by_span: dict[str, float] = {}
    for ga, gb in gaps:
        left = [(ga, gb)]
        for _len, name, s, e in spans:
            nxt = []
            for a, b in left:
                lo, hi = max(a, s), min(b, e)
                if hi > lo:
                    by_span[name] = by_span.get(name, 0.0) + (hi - lo) / 1e9
                    if a < lo:
                        nxt.append((a, lo))
                    if hi < b:
                        nxt.append((hi, b))
                else:
                    nxt.append((a, b))
            left = nxt
            if not left:
                break
        rest = sum(b - a for a, b in left) / 1e9
        if rest > 0:
            by_span["unattributed"] = by_span.get("unattributed", 0.0) + rest
    rank = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": sum(per_device) / len(per_device),
        "window_s": (w1 - w0) / 1e9,
        "per_device_busy_s": per_device,
        "device_ops": rank(ops),
        "modules": modules,
        "idle_gaps": rank(by_span),
    }


def step_ms(reduced: dict, module: str, ticks_per_execution: int):
    """Device milliseconds per tick of program `module`: the mean duration of
    its whole executions inside the traced window over the ticks in one;
    None where the window holds no such execution."""
    m = reduced["modules"].get(module)
    if not m or not m["count"]:
        return None
    return m["seconds"] / m["count"] / ticks_per_execution * 1e3


def sync_offset_ns(planes: dict, sync_perf_s: float) -> int:
    """Trace-timeline nanoseconds minus perf_counter nanoseconds, from the
    `bench_sync` annotation dropped right after the profiler started (its
    start stands for the perf_counter reading `sync_perf_s`)."""
    for name, start, _dur in planes.get("/host:CPU", {}).get("annotations", []):
        if name == SYNC_NAME:
            return int(start - sync_perf_s * 1e9)
    raise ValueError("the trace holds no bench_sync annotation; host spans "
                     "cannot be placed on its timeline")
