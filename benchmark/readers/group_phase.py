"""Host milliseconds in one phase of the stream groups' chunk path — the
program's own `rtap.group.*` annotation named by `phase` — over the traced
window: `per` "chunk" is the mean per chunk (replay), `per` "tick" the sum
over the groups, mean over ticks (live)
(benchmark/scoped_trace.py:phase_ms)."""

from benchmark.scoped_trace import of_record, phase_ms


def read(record: dict, definition: dict):
    found = of_record(record)
    if found is None:
        return None
    return phase_ms(found[0], definition["phase"], definition["per"], found[1])
