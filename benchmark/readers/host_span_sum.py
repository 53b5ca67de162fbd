"""Seconds under the loop-track spans named `span` in the record's
`host_spans` — what the program's own TraceRecorder held on the loop's track,
e.g. the AOT warm-up's `aot_warm` spans, one per program executed before
tick 0. A program that records none reads nothing."""


def total_s(host_spans, name: str) -> float | None:
    durs = [dur for n, _t0, dur in host_spans if n == name]
    return float(sum(durs)) if durs else None


def read(record: dict, definition: dict):
    return total_s(record.get("host_spans") or (), definition["span"])
