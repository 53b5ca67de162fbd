"""The device's line of a profiler trace against the host's: two clocks.

The profiler places the device's events (`XLA Modules`, `XLA Ops`) on the
host's timeline, and the placement is off by a run's own constant of 2-3 ms
that drifts a few microseconds a second (my chip runs, PR 39: a program
"started" 0.75 ms before the call that enqueued it; over ten live ticks the
smallest fetch end - device end of a tick read 2.88 ... 2.73 ms in one run
and 1.94 ... 1.85 in the next). Anything that subtracts a host instant from
a device instant has to place the device first.

`chain` pairs each chunk's `rtap.group.enqueue` and `rtap.group.fetch`
annotations (same `group`, same `seq`) with its execution on the `XLA
Modules` line: one chip runs its programs in the order they were enqueued,
so the k-th enqueue to END is the k-th execution to START (aligned from the
window's LAST execution, so a program the tracer clipped at its start — it
holds the device's first recorded op and its enqueue precedes the window —
drops out). Causality then brackets the offset `d` to add to the device's
times, per round (the chunks of one `seq`: the groups step in lockstep): no
execution starts before its enqueue does, `d >= max(enqueue start - device
start)`, and none ends after its fetch, `d <= min(fetch end - device end)`.
The upper end is the tight one — a fetch that was blocked on its program
returns within tens of microseconds of the program's end, and a round has
many such fetches — so the offset applied is the bracket's upper end: the
round's quickest fetch ends when its program does (its tail reads 0, the
others' their excess over it; the error is that one fetch's true tail). An
empty bracket means the pairing itself is wrong."""

from __future__ import annotations

import bisect
import re

from benchmark.trace_reduce import DEVICE_PLANE, _union

ENQUEUE, FETCH = "rtap.group.enqueue", "rtap.group.fetch"


class NoChain(ValueError):
    """The trace's enqueues, executions and fetches do not pair up."""


def chain(planes: dict, module: str, window_ns) -> list | None:
    """-> [{group, seq, enqueue_start, enqueue_end, device_start, device_end
    (placed on the host's clock), fetch_start, fetch_end, offset, offset_low,
    offset_high, raw_start, raw_end}] (ns), one per whole execution of
    `module` in the window, in order; None for a trace with no enqueue
    annotation; NoChain where they do not pair up."""
    w0, w1 = window_ns
    notes = planes.get("/host:CPU", {}).get("annotations", [])

    def chunks(name):
        return sorted(((s, s + d, a.get("group"), a.get("seq"))
                       for n, s, d, a in notes
                       if n == name and s >= w0 and s + d <= w1),
                      key=lambda e: e[1])

    enqueues = chunks(ENQUEUE)
    if not enqueues:
        return None
    fetches = {(g, q): (s, e) for s, e, g, q in chunks(FETCH)}
    devices = [p for p in sorted(planes) if DEVICE_PLANE.match(p)]
    if len(devices) != 1:
        raise NoChain(f"{len(devices)} device planes: the order of "
                      "enqueues is the order of executions on one chip only")
    lines = planes[devices[0]]
    runs = sorted((s, s + d) for n, s, d in lines.get("XLA Modules", [])
                  if re.sub(r"\(\d+\)$", "", n) == module and s + d <= w1)
    # one execution more than enqueues, and it holds the device's first
    # recorded op: a program that was running when the tracer started
    first_op = min((e[1] for e in lines.get("XLA Ops", [])),
                   default=float("inf"))
    clipped = len(runs) == len(enqueues) + 1 and \
        runs[0][0] <= first_op <= runs[0][1]
    whole = runs[1:] if clipped else runs
    if len(whole) != len(enqueues):
        raise NoChain(
            f"{len(enqueues)} enqueue(s) in the window and {len(whole)} whole "
            f"execution(s) of {module}")
    out = []
    for (q0, q1, group, seq), (d0, d1) in zip(enqueues, whole):
        if (group, seq) not in fetches:
            raise NoChain(f"group {group!r} seq {seq}: no fetch in the window")
        f0, f1 = fetches[(group, seq)]
        out.append({"group": group, "seq": seq, "enqueue_start": q0,
                    "enqueue_end": q1, "raw_start": d0, "raw_end": d1,
                    "fetch_start": f0, "fetch_end": f1})
    rounds: dict = {}
    for t in out:
        rounds.setdefault(t["seq"], []).append(t)
    for seq, members in rounds.items():
        low = max(t["enqueue_start"] - t["raw_start"] for t in members)
        high = min(t["fetch_end"] - t["raw_end"] for t in members)
        if low > high:
            raise NoChain(
                f"seq {seq}: an execution starts {low / 1e3:.1f} us before its "
                f"enqueue does, so the device's clock is at least that much "
                f"behind, and another ends {high / 1e3:.1f} us before its "
                "fetch does, so at most that: no offset allows both")
        for t in members:
            t.update(offset=high, offset_low=low, offset_high=high,
                     device_start=t["raw_start"] + high,
                     device_end=t["raw_end"] + high)
    return out


def of_record(record: dict, module: str, found) -> list | None:
    """`chain` of the record's run, once per module, with a line for the
    run's log; None where there is nothing to pair or it does not pair."""
    cache = record.setdefault("group_chains", {})
    if module in cache:
        return cache[module]
    try:
        triples = cache[module] = chain(found[0], module, found[1])
    except NoChain as e:
        print(f"[device_clock] nothing paired: {e}", flush=True)
        cache[module] = None
        return None
    if triples:
        rounds = {t["seq"]: t for t in triples}
        firsts: dict = {}
        for t in triples:
            firsts.setdefault(t["seq"], t)
        lead = [t["device_start"] - t["enqueue_start"]
                for t in firsts.values()]
        print(f"[device_clock] {len(triples)} (enqueue, execution, fetch) "
              f"triples of {module} matched in the traced window; offset of "
              "the device's line on the host's clock, us, seq: [least, most] "
              "causality allows (the most is applied): " + "; ".join(
                  f"{q}: [{t['offset_low'] / 1e3:.1f}, "
                  f"{t['offset_high'] / 1e3:.1f}]"
                  for q, t in sorted(rounds.items())[-32:])
              + f"; a round's first program starts "
              f"{sum(lead) / len(lead) / 1e6:.3f} ms after its enqueue began "
              f"(min {min(lead) / 1e6:.3f}, max {max(lead) / 1e6:.3f})",
              flush=True)
    return triples


def busy_on_host_clock(planes: dict, triples: list | None) -> list:
    """The merged, sorted intervals in which an `XLA Ops` event runs, each
    op moved by the offset of the execution it belongs to (the nearest one
    where it belongs to none; 0 where nothing was paired)."""
    ops = [(e[1], e[1] + e[2]) for name in planes if DEVICE_PLANE.match(name)
           for e in planes[name].get("XLA Ops", [])]
    if not triples:
        return _union(ops)
    starts = [t["raw_start"] for t in triples]
    moved = []
    for a, b in ops:
        t = triples[max(0, bisect.bisect_right(starts, a) - 1)]
        moved.append((a + t["offset"], b + t["offset"]))
    return _union(moved)
