"""One kernel's share of its memory roofline, in %: the least time its
scope's leaves take to be read and written once at the chip's peak HBM rate
(bytes from shapes, benchmark/kernel_bytes.py) over the device time the scope
took per group-tick (benchmark/scoped_trace.py:by_scope). Memory-bound."""

from benchmark.kernel_bytes import kernel_floor_seconds
from benchmark.scoped_trace import scope_table


def read(record: dict, definition: dict):
    table = scope_table(record, definition["module"])
    ms = None if table is None else table.get(definition["scope"])
    if not ms:
        return None
    floor = kernel_floor_seconds(definition["scope"], record["config"]["model"],
                                 record["config"]["layout"]["group_size"],
                                 record["device_kind"])
    return 100.0 * floor / (ms / 1e3)
