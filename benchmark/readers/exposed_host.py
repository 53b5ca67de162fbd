"""The live tick's host time that the device does not hide.

Per tick, over the interval `score_p50_ms` times from outside — from the end
of `rtap.ingest.snapshot` (the program holds the rows) to the end of
`rtap.loop.emit` (their scores are emitted) — the nanoseconds in which no
`XLA Ops` event runs on the device; mean over the traced ticks, in ms. Host
work behind a running program costs the tick nothing; this is what is left,
and what a host-side change can win. All in one trace — but the device's
line and the host's are two clocks 2-3 ms apart, so the device's ops are
first placed on the host's clock (benchmark/device_clock.py: by the chunks'
enqueue -> execution -> fetch chain of `module`); without that the lead-in
before a tick's first program would be booked under its last fetch.

For the run's log the reader also splits that time by the innermost `rtap.*`
host span covering it (the shortest, as trace_reduce attributes idle
gaps), and states the identity exposed + device busy = emit end - snapshot
end, whose mean is the `[live] per tick, snapshot -> emitted` line's.

A trace without the two spans (a commit before the seam) reads nothing."""

import bisect

from benchmark import device_clock
from benchmark.scoped_trace import of_record

START, END = "rtap.ingest.snapshot", "rtap.loop.emit"


NO_SPAN = "under no rtap.* span"


def _gaps(lo: float, hi: float, busy: list, starts: list) -> list:
    """[lo, hi] minus the merged, sorted intervals `busy` (`starts`: their
    start times, for the bisection)."""
    out, cursor = [], lo
    for a, b in busy[max(0, bisect.bisect_right(starts, lo) - 1):]:
        if a >= hi:
            break
        if b <= cursor:
            continue
        if a > cursor:
            out.append((cursor, a))
        cursor = b
    if cursor < hi:
        out.append((cursor, hi))
    return out


def _innermost(lo: float, hi: float, spans: list) -> list:
    """[lo, hi] cut at every span edge -> [(a, b, name of the shortest span
    covering [a, b])], in order. `spans`: (start, end, name)."""
    cuts = sorted({lo, hi} | {x for s, e, _n in spans for x in (s, e)
                              if lo < x < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(e - s, name) for s, e, name in spans if s <= a and b <= e]
        out.append((a, b, min(over)[1] if over else NO_SPAN))
    return out


def intervals(planes: dict, window_ns) -> dict:
    """{tick: (end of its snapshot, end of its emit)} for the ticks whose
    interval lies inside the window."""
    w0, w1 = window_ns
    ends: dict = {}
    for name, s, d, args in planes.get("/host:CPU", {}).get("annotations", []):
        if name in (START, END) and "tick" in args:
            ends.setdefault(args["tick"], {})[name] = s + d
    return {k: (e[START], e[END]) for k, e in ends.items()
            if START in e and END in e and w0 <= e[START] <= e[END] <= w1}


def per_tick(planes: dict, window_ns, triples: list | None = None):
    """-> {tick: {"interval_ns", "exposed_ns", "by_span": {name: ns}}} for
    the ticks whose interval lies inside the window; None where the trace
    holds no such tick. `triples`: device_clock.chain's, by which the
    device's ops are placed on the host's clock (None: as recorded)."""
    ticks = intervals(planes, window_ns)
    if not ticks:
        return None
    notes = planes["/host:CPU"]["annotations"]
    busy = device_clock.busy_on_host_clock(planes, triples)
    starts = [a for a, _b in busy]
    spans = [(s, s + d, name) for name, s, d, _a in notes
             if name.startswith("rtap.") and d > 0]
    out = {}
    for k, (lo, hi) in sorted(ticks.items()):
        # two sorted lists walked together: the gaps of the device, the
        # stretches of the interval with their innermost host span
        stretches = _innermost(lo, hi, [sp for sp in spans
                                        if sp[0] < hi and sp[1] > lo])
        by_span: dict = {}
        i = 0
        for a, b in _gaps(lo, hi, busy, starts):
            while stretches[i][1] <= a:
                i += 1
            j = i
            while j < len(stretches) and stretches[j][0] < b:
                x, y, name = stretches[j]
                by_span[name] = by_span.get(name, 0.0) + min(b, y) - max(a, x)
                j += 1
        out[k] = {"interval_ns": hi - lo,
                  "exposed_ns": sum(by_span.values()), "by_span": by_span}
    return out


def read(record: dict, definition: dict):
    found = of_record(record)
    if found is None:
        return None
    if not intervals(*found):
        return None  # none of the program's spans: nothing to place either
    triples = device_clock.of_record(record, definition["module"], found)
    ticks = per_tick(found[0], found[1], triples)
    n = len(ticks)
    exposed = sum(t["exposed_ns"] for t in ticks.values()) / n / 1e6
    interval = sum(t["interval_ns"] for t in ticks.values()) / n / 1e6
    by_span: dict = {}
    for t in ticks.values():
        for name, ns in t["by_span"].items():
            by_span[name] = by_span.get(name, 0.0) + ns / n / 1e6
    print(f"[{definition['name']}] {n} traced ticks {min(ticks)}..{max(ticks)}"
          f": snapshot end -> emit end {interval:.3f} ms a tick = device busy "
          f"{interval - exposed:.3f} + exposed host {exposed:.3f}"
          + ("" if triples else " (the device's line as recorded: no chain "
             "to place it by)") + "; exposed, by "
          "the innermost host span over it, ms a tick: " + ", ".join(
              f"{name} {ms:.3f}" for name, ms in sorted(
                  by_span.items(), key=lambda kv: -kv[1]))
          + "; per tick, interval/exposed ms: " + " ".join(
              f"{t['interval_ns'] / 1e6:.1f}/{t['exposed_ns'] / 1e6:.2f}"
              for t in ticks.values()), flush=True)
    return exposed
