"""One kernel's share of its memory roofline, in %: the least time its
scope's leaves take to be read and written once at the chip's peak HBM rate
(bytes from shapes, benchmark/kernel_bytes.py) over the device time the scope
AND its sub-scopes took per group-tick (benchmark/scoped_trace.py:by_scope;
`rtap.tm` is every `rtap.tm.*`, as readers/dense_roofline.py reads a scope).
Memory-bound."""

from benchmark.kernel_bytes import kernel_floor_seconds
from benchmark.scoped_trace import scope_table, scope_with_subscopes_ms


def read(record: dict, definition: dict):
    table = scope_table(record, definition["module"])
    ms = scope_with_subscopes_ms(table, definition["scope"]) if table else None
    if not ms:
        return None
    floor = kernel_floor_seconds(definition["scope"], record["config"]["model"],
                                 record["config"]["layout"]["group_size"],
                                 record["device_kind"])
    return 100.0 * floor / (ms / 1e3)
