"""Host microseconds under one of the program's `rtap.*` annotations (`span`
in the definition) for each unit of work the span says it did: the sum of
the span's time over the sum of its argument `count`, over every thread's
events wholly inside the traced window — the parser's cost a value, from
`rtap.ingest.feed` and the `values` it wrote. A program whose span carries no
such argument (a commit before it did) reads nothing, as does a window in
which the span did no work."""

from benchmark.scoped_trace import of_record


def read(record: dict, definition: dict):
    found = of_record(record)
    if found is None:
        return None
    planes, (w0, w1) = found
    events = [e for e in planes.get("/host:CPU", {}).get("annotations", [])
              if e[0] == definition["span"] and e[1] >= w0
              and e[1] + e[2] <= w1]
    counts = [e[3].get(definition["count"]) for e in events]
    if None in counts:
        return None
    done = sum(int(c) for c in counts)
    return sum(e[2] for e in events) / 1e3 / done if done else None
