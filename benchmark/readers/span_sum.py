"""Host milliseconds under one of the program's `rtap.*` annotations (`span`
in the definition), summed over every thread's events inside the traced
window and divided by `per`: "tick" — the `rtap.loop.tick` annotations the
window holds (ingest busy time a slot) — or "second" — the window's length
(garbage collection a second of stepping).

A name the program's own vocabulary declares (`rtap_tpu/obs/trace.py:SPANS`,
looked up in the modules the run has already loaded, never imported here)
and the window holds none of reads 0: no collection ran. A name it does not
declare — a commit before the seam — reads nothing: the metric is left out.
`log_by` in the definition names an argument of the span by which the run's
log splits count and time (collections by `generation`)."""

import sys

from benchmark.scoped_trace import of_record

TICK = "rtap.loop.tick"


def inside(planes: dict, name: str, window_ns) -> list:
    """The host plane's `name` annotations wholly inside the window."""
    w0, w1 = window_ns
    return [e for e in planes.get("/host:CPU", {}).get("annotations", [])
            if e[0] == name and e[1] >= w0 and e[1] + e[2] <= w1]


def total_ms(planes: dict, name: str, per: str, window_ns,
             vocabulary=()) -> float | None:
    events = inside(planes, name, window_ns)
    if not events and name not in vocabulary:
        return None
    total = sum(e[2] for e in events) / 1e6
    if per == "second":
        return total / ((window_ns[1] - window_ns[0]) / 1e9)
    if per == "tick":
        ticks = len(inside(planes, TICK, window_ns))
        return total / ticks if ticks else None
    raise ValueError(f"span_sum reader: unknown 'per' {per!r}")


def read(record: dict, definition: dict):
    found = of_record(record)
    if found is None:
        return None
    planes, window = found
    name = definition["span"]
    program = sys.modules.get("rtap_tpu.obs.trace")
    value = total_ms(planes, name, definition["per"], window,
                     getattr(program, "SPANS", ()))
    key = definition.get("log_by")
    if key and value is not None:  # for the run's log: the split by an argument
        split: dict = {}
        for _n, _s, d, args in inside(planes, name, window):
            part = split.setdefault(args.get(key), [0, 0.0])
            part[0] += 1
            part[1] += d / 1e6
        print(f"[{definition['name']}] {name} in the traced window, {key}: "
              "count, ms: " + ("; ".join(
                  f"{k}: {n}, {ms:.3f}" for k, (n, ms) in sorted(
                      split.items(), key=lambda kv: str(kv[0]))) or "none"),
              flush=True)
    return value
