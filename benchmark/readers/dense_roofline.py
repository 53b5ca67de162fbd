"""Shares of the memory roofline for the dense-pool family, in %
(benchmark/kernel_bytes_dense.py has the bytes), selected by `what`:

    kernel   the floor of the kernel under `scope` over the device time of
             that scope AND its sub-scopes (`rtap.tm` is every `rtap.tm.*`:
             activate, learn, learn.rows, dendrite) per group-tick
    step     the floor of the whole tick (state read once, written once)
             over the sum of every scope's device time per group-tick —
             the step's true time (PERF.md s7, question 11)

Device time from benchmark/scoped_trace.py:by_scope. A program that carries
no scope, or a trace with no whole execution, gives nothing to read."""

from benchmark.kernel_bytes_dense import (
    kernel_floor_seconds, step_floor_seconds)
from benchmark.scoped_trace import scope_table, scope_with_subscopes_ms


def read(record: dict, definition: dict):
    table = scope_table(record, definition["module"])
    if not table:
        return None
    model = record["config"]["model"]
    group_size = record["config"]["layout"]["group_size"]
    if definition["what"] == "step":
        ms = sum(table.values())
        floor = step_floor_seconds(model, group_size, record["device_kind"])
    elif definition["what"] == "kernel":
        scope = definition["scope"]
        ms = scope_with_subscopes_ms(table, scope)
        floor = kernel_floor_seconds(scope, model, group_size,
                                     record["device_kind"])
    else:
        raise ValueError(
            f"dense_roofline reader: unknown 'what' {definition['what']!r}")
    return 100.0 * floor / (ms / 1e3) if ms else None
