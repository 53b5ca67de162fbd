"""The program's own names in a profiler trace: device time per `rtap.*`
scope of the fused step, host time per `rtap.group.*` phase of a stream
group.

The program writes both (rtap_tpu/ops/step.py `jax.named_scope`,
rtap_tpu/service/registry.py `jax.profiler.TraceAnnotation`); this module only
matches the prefix `rtap.` and knows no vocabulary.

Where the names are in a TPU trace (JAX 0.9, TPU v5 lite; my chip run, PR 25):
an `XLA Ops` event's own stats are timing only, but its *event metadata* in
the .xplane.pb carries `tf_op` — the op's `op_name`, e.g.
`jit(chunk_step)/while/body/closed_call/vmap(jit(sp_step))/rtap.sp.overlap/gather`
(a fusion carries its root instruction's). `jax.profiler.ProfileData` does not
surface metadata stats, so `load` walks the protobuf wire format itself; the
few message and field numbers it needs are tsl/profiler/protobuf/xplane.proto's.
The host plane's annotations keep their keyword arguments as event stats.

`load` gives a plain event list ({plane: {line: [[name, start_ns, dur_ns,
...], ...]}}) — the one reading of a run's trace: trace_reduce.reduce takes
busy, idle and the `breakdown` from it, `by_scope` and `phase_ms` the
program's names — so all three can be checked without a chip on a reduced
recording (benchmark/fixtures/trace_v5e_scoped.json)."""

from __future__ import annotations

import glob
import os
import re
import struct

from benchmark.registry import REPO
from benchmark.trace_reduce import (
    DEVICE_PLANE, SYNC_NAME, UNSCOPED, _self_times, scope_of)


class NoScopes(ValueError):
    """The trace holds executions of the program but not one `rtap.` scope:
    the program does not name its work (a commit before the scopes), or the
    profiler stopped carrying op names."""


# ---- the .xplane.pb, by its wire format ----

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message: an int for varints, bytes for
    length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"xplane: unsupported wire type {wire}")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(stats: list[bytes], stat_names: dict[int, str]) -> dict:
    """XStat messages -> {name: value} (strings, ints, floats; a `ref`
    value names a stat-metadata entry that holds the string)."""
    out = {}
    for raw in stats:
        name = value = None
        for f, v in _fields(raw):
            if f == 1:
                name = stat_names.get(v)
            elif f == 2:
                value = struct.unpack("<d", v)[0]
            elif f == 3:
                value = v
            elif f == 4:
                value = _signed(v)
            elif f in (5, 6):
                value = v.decode("utf-8", "replace")
            elif f == 7:
                value = stat_names.get(v, "")
        if name is not None:
            out[name] = value
    return out


def _plane(buf: bytes) -> dict:
    """One XPlane -> {name, lines: [(line name, timestamp_ns, [event bytes])],
    events: {metadata id: (name, [stat bytes])}, stat_names: {id: name}}."""
    plane = {"name": "", "lines": [], "events": {}, "stat_names": {}}
    for f, v in _fields(buf):
        if f == 2:
            plane["name"] = v.decode()
        elif f == 3:
            name, t0, events = "", 0, []
            for lf, lv in _fields(v):
                if lf == 2:
                    name = lv.decode()
                elif lf == 3:
                    t0 = _signed(lv)
                elif lf == 4:
                    events.append(lv)
            plane["lines"].append((name, t0, events))
        elif f in (4, 5):  # map entries: key = 1, value = 2
            key, value = 0, b""
            for mf, mv in _fields(v):
                if mf == 1:
                    key = mv
                elif mf == 2:
                    value = mv
            if f == 5:
                plane["stat_names"][key] = next(
                    (sv.decode() for sf, sv in _fields(value) if sf == 2), "")
            else:
                name, stats = "", []
                for ef, ev in _fields(value):
                    if ef == 2:
                        name = ev.decode("utf-8", "replace")
                    elif ef == 5:
                        stats.append(ev)
                plane["events"][key] = (name, stats)
    return plane


def _events(line: tuple):
    """(metadata id, start_ns, dur_ns, [stat bytes]) of one line's events."""
    _name, t0, raw_events = line
    for raw in raw_events:
        meta = offset_ps = dur_ps = 0
        stats = []
        for f, v in _fields(raw):
            if f == 1:
                meta = v
            elif f == 2:
                offset_ps = _signed(v)
            elif f == 3:
                dur_ps = _signed(v)
            elif f == 4:
                stats.append(v)
        yield meta, t0 + offset_ps / 1e3, dur_ps / 1e3, stats


def newest_log_dir(root: str = REPO) -> str | None:
    """The cell directory under `<root>/.bench_trace/` whose trace is the
    newest (benchmark/run.py writes a run's trace to `.bench_trace/<cell>`
    and removes that cell's old one first)."""
    paths = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not paths:
        return None
    # <cell dir>/plugins/profile/<time>/<host>.xplane.pb
    return os.path.normpath(os.path.join(
        max(paths, key=os.path.getmtime), *[os.pardir] * 4))


def load(log_dir: str) -> dict:
    """The newest trace under a `jax.profiler.start_trace(log_dir)` directory
    -> {"/device:TPU:<n>": {"XLA Modules": [[name, start_ns, dur_ns], ...],
    "XLA Ops": [[hlo text, start_ns, dur_ns, op_name], ...]},
    "/host:CPU": {"annotations": [[name, start_ns, dur_ns, {args}], ...]}};
    the annotations kept are the program's `rtap.*` and the benchmark's
    sync mark."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    with open(paths[-1], "rb") as f:
        space = f.read()
    planes: dict = {}
    for f, raw in _fields(space):
        if f != 1:
            continue
        plane = _plane(raw)
        device = DEVICE_PLANE.match(plane["name"])
        if not device and plane["name"] != "/host:CPU":
            continue
        names = plane["stat_names"]
        for line in plane["lines"]:
            if device and line[0] == "XLA Modules":
                planes.setdefault(plane["name"], {})["XLA Modules"] = [
                    [plane["events"][m][0], s, d]
                    for m, s, d, _ in _events(line)]
            elif device and line[0] == "XLA Ops":
                op_names = {m: _stats(st, names).get("tf_op", "")
                            for m, (_n, st) in plane["events"].items()}
                planes.setdefault(plane["name"], {})["XLA Ops"] = [
                    [plane["events"][m][0], s, d, op_names[m]]
                    for m, s, d, _ in _events(line)]
            elif not device:
                kept = planes.setdefault(plane["name"], {}).setdefault(
                    "annotations", [])
                for m, s, d, st in _events(line):
                    name = plane["events"].get(m, ("", []))[0]
                    if name.startswith("rtap.") or name == SYNC_NAME:
                        kept.append([name, s, d, _stats(st, names)])
    return planes


# ---- the reductions ----

def traced_window(planes: dict, window_s: float) -> tuple[float, float]:
    """The benchmark's traced window on the trace's timeline: from its sync
    annotation, `window_s` long (run.py's `reduce_trace` takes the same)."""
    for name, start, *_ in planes.get("/host:CPU", {}).get("annotations", []):
        if name == SYNC_NAME:
            return start, start + window_s * 1e9
    raise ValueError(f"the trace holds no {SYNC_NAME} annotation")


def by_scope(planes: dict, module: str, ticks_per_execution: int,
             window_ns: tuple[float, float] | None = None) -> dict | None:
    """Device milliseconds per group-tick per scope: the self time (a `while`
    op's time belongs to its body) of the ops inside WHOLE executions of
    program `module`, over executions x ticks in one. An op belongs to the
    innermost `rtap.` scope of its name, or to "unscoped"; a fusion carries
    its root instruction's name, whatever XLA numbered it.

    Whole: inside `window_ns`, and not the execution that holds the device's
    first recorded op — a program that was running when the profiler started
    is clipped to the tracer's own start (it begins after the sync mark, so
    the window alone does not catch it), and a clipped program counted as
    one would read every scope low. Where the profiler started on an idle
    device this drops one whole execution, which biases nothing.

    None where the trace holds no whole execution; NoScopes where it holds
    executions but no scope at all."""
    w0, w1 = window_ns or (float("-inf"), float("inf"))
    total_ns: dict[str, float] = {}
    executions = 0
    for name in sorted(p for p in planes if DEVICE_PLANE.match(p)):
        lines = planes[name]
        ops = sorted(lines.get("XLA Ops", []), key=lambda e: e[1])
        first_op = ops[0][1] if ops else float("inf")
        runs = sorted((s, s + d) for n, s, d in lines.get("XLA Modules", [])
                      if re.sub(r"\(\d+\)$", "", n) == module
                      and s >= w0 and s + d <= w1
                      and not s <= first_op <= s + d)
        executions += len(runs)
        inside, k = [], 0
        for _text, s, d, op_name in ops:
            while k < len(runs) and runs[k][1] < s:
                k += 1
            if k < len(runs) and runs[k][0] <= s and s + d <= runs[k][1]:
                inside.append([scope_of(op_name), s, d])
        for scope, self_ns in _self_times(inside):
            total_ns[scope] = total_ns.get(scope, 0.0) + self_ns
    if not executions:
        return None
    if not any(s != UNSCOPED for s in total_ns):
        raise NoScopes(f"{executions} execution(s) of {module} and not one "
                       "rtap. scope among their ops")
    per = executions * ticks_per_execution * 1e6
    return {scope: ns / per for scope, ns in total_ns.items()}


def phase_ms(planes: dict, phase: str, per: str,
             window_ns: tuple[float, float] | None = None) -> float | None:
    """Host milliseconds in the stream groups' `phase` annotation
    (`rtap.group.*`): `per` "chunk" is the mean over the annotation's events
    (one per chunk dispatched or collected); `per` "tick" sums over the
    groups, mean over ticks (events / distinct `group` arguments: the
    groups step in lockstep, one chunk each per tick). None where the trace
    holds no such annotation."""
    w0, w1 = window_ns or (float("-inf"), float("inf"))
    events = [(d, args.get("group"))
              for n, s, d, args in planes.get("/host:CPU", {}).get(
                  "annotations", [])
              if n == phase and s >= w0 and s + d <= w1]
    if not events:
        return None
    total_ms = sum(d for d, _g in events) / 1e6
    if per == "chunk":
        return total_ms / len(events)
    if per == "tick":
        return total_ms / (len(events) / len({g for _d, g in events}))
    raise ValueError(f"phase_ms: unknown 'per' {per!r}")


# ---- for the readers: one parse per run ----

def of_record(record: dict) -> tuple[dict, tuple[float, float]] | None:
    """(event list, traced window) of the run `record` describes — a reader
    gets only the record, so the trace is found where run.py wrote it; parsed
    once and kept on the record. None for an untraced run."""
    if record.get("trace") is None:
        return None
    if "scoped_planes" not in record:
        log_dir = newest_log_dir()
        if log_dir is None:
            raise FileNotFoundError(
                f"--trace 1 but no .xplane.pb under {REPO}/.bench_trace")
        record["scoped_planes"] = load(log_dir)
    planes = record["scoped_planes"]
    return planes, traced_window(planes, record["trace"]["window_s"])


def scope_table(record: dict, module: str) -> dict | None:
    """`by_scope` of the record's run, once per module. A program that
    carries no scope (a commit before them) has nothing to read: None."""
    found = of_record(record)
    if found is None:
        return None
    cache = record.setdefault("scope_tables", {})
    if module not in cache:
        try:
            cache[module] = by_scope(found[0], module, record["chunk_ticks"],
                                     found[1])
        except NoScopes:
            cache[module] = None
    return cache[module]


def scope_with_subscopes_ms(table: dict, scope: str) -> float:
    """Device ms per group-tick of `scope` and every scope beneath it
    (`rtap.tm.learn.rows` is part of `rtap.tm.learn`, and both of `rtap.tm`):
    a share of a floor divides by all the time its bytes were moved in,
    whichever of those names a fusion's root filed it under."""
    return sum(ms for name, ms in table.items()
               if name == scope or name.startswith(scope + "."))
