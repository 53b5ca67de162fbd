"""From a profiler trace to numbers: device busy/idle, per-op time, the
program executions, and idle gaps attributed to what the host was doing.

Two steps, so the arithmetic can be checked without a chip:
benchmark/scoped_trace.py:load turns the profiler's .xplane.pb into a plain
event list ({plane: {line: [[name, start_ns, dur_ns, ...], ...]}}; an `XLA
Ops` event carries its `op_name` fourth), and `reduce` works on that list
alone (tests/benchmark/test_trace_reduce.py runs it on reduced recordings of
real traces, benchmark/fixtures/trace_v5e_*.json).

What a TPU trace looks like (JAX 0.9, TPU v5 lite): plane `/device:TPU:<n>`
has the lines `XLA Modules` (one event per program execution, named
`jit_<fn>(<hash>)`) and `XLA Ops` (one event per HLO op, named by its HLO
text `%<op> = <type> ...`); plane `/host:CPU` has one line per thread, the
main thread's line carrying `jax.profiler.TraceAnnotation`s. All share one
timeline in nanoseconds."""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SYNC_NAME = "bench_sync"


SCOPE = re.compile(r"rtap\.[a-z_]+(?:\.[a-z_]+)*")
UNSCOPED = "unscoped"


def scope_of(op_name: str) -> str:
    """The innermost `rtap.` scope in an op's name (under vmap/scan/cond JAX
    wraps name-stack entries: `vmap(rtap.encode)`, `while/body/...`), or
    "unscoped"."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def op_label(hlo_text: str, op_name: str = "") -> str:
    """`%fusion.194 = s32[1024,256,192]{...} fusion(...)` named
    `.../rtap.tm.learn/select_n` -> `rtap.tm.learn/fusion:s32[1024,256,192]`:
    the scope that owns the op (`-` for none), its kind and result type,
    without XLA's numbering (which changes with every compile), layout or
    operands; short enough for a ledger line. Equal work under one scope
    shares a label, and a label means the same at parent and change."""
    scope = scope_of(op_name)
    scope = "-" if scope == UNSCOPED else scope
    m = re.match(r"%?([^\s=]+?)(?:\.\d+)?\s*=\s*\(?([A-Za-z0-9_]+\[[^\]]*\])?",
                 hlo_text)
    if not m:
        return f"{scope}/{hlo_text}"[:64]
    return (f"{scope}/{m.group(1)}"
            + (":" + m.group(2) if m.group(2) else ""))[:64]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _self_times(events: list) -> list[tuple[str, int]]:
    """(name, self ns) per [name, start_ns, dur_ns] event of one line: its
    duration minus the events nested inside it (a `while` op spans its whole
    loop body; the time belongs to the ops of the body)."""
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
    labels: dict[tuple, str] = {}  # (hlo text, op_name) -> op_label
    first_busy: list[tuple[int, int]] = []
    for d in devices:
        lines = planes[d]
        op_events = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        clipped = [(max(e[1], w0), min(e[1] + e[2], w1)) for e in op_events
                   if e[1] + e[2] > w0 and e[1] < w1]
        busy = _union(clipped)
        per_device.append(sum(b - a for a, b in busy) / 1e9)
        if d == devices[0]:
            first_busy = busy
        # an op event is [hlo text, start, dur] or, from scoped_trace.load,
        # [hlo text, start, dur, op_name]
        for key, self_ns in _self_times(
                [[(e[0], e[3] if len(e) > 3 else ""), e[1], e[2]]
                 for e in lines.get("XLA Ops", [])
                 if e[1] + e[2] > w0 and e[1] < w1]):
            if key not in labels:
                labels[key] = op_label(*key)
            ops[labels[key]] = ops.get(labels[key], 0.0) + self_ns / 1e9
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
    # shortest covering span first (ties: the caller's order). The gaps are
    # disjoint and ascending, so the spans that can overlap one are kept by
    # a sweep: a gap is held against those alone, not against every span
    # (gaps x spans is minutes on a window of 250 programs)
    spans = sorted(((s, e - s, i, name, e)
                    for i, (name, s, e) in enumerate(host_spans_ns) if e > s))
    by_span: dict[str, float] = {}
    overlapping: list[tuple] = []
    nxt_span = 0
    for ga, gb in gaps:
        while nxt_span < len(spans) and spans[nxt_span][0] < gb:
            overlapping.append(spans[nxt_span])
            nxt_span += 1
        overlapping = [sp for sp in overlapping if sp[4] > ga]
        left = [(ga, gb)]
        for s, _len, _i, name, e in sorted(overlapping, key=lambda sp: sp[1:3]):
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
    for name, start, *_ in planes.get("/host:CPU", {}).get("annotations", []):
        if name == SYNC_NAME:
            return int(start - sync_perf_s * 1e9)
    raise ValueError("the trace holds no bench_sync annotation; host spans "
                     "cannot be placed on its timeline")
