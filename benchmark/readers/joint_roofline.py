"""Several scopes' joint share of one memory roofline, in %: the least time
the leaves under `floor` take at the chip's peak HBM rate (bytes from shapes,
benchmark/kernel_bytes_dense.py) over the device time of every scope in
`scopes`, their sub-scopes included, summed per group-tick
(benchmark/scoped_trace.py:by_scope).

For kernels the compiler fuses across their scopes: with the model-health
reducer on, XLA roots the temporal memory's pool sweep under
`rtap.reduce.health`, so neither scope's own time means what its name says
and only their sum does — over the TM's bytes, since the pools are read once
for both (PERF.md s3). A step that ran without every one of the scopes gives
nothing to read: its share is the single scope's (readers/dense_roofline.py)."""

from benchmark.kernel_bytes_dense import kernel_floor_seconds
from benchmark.scoped_trace import scope_table, scope_with_subscopes_ms


def read(record: dict, definition: dict):
    table = scope_table(record, definition["module"])
    if not table:
        return None
    each = [scope_with_subscopes_ms(table, s) for s in definition["scopes"]]
    if not all(each):
        return None
    floor = kernel_floor_seconds(definition["floor"], record["config"]["model"],
                                 record["config"]["layout"]["group_size"],
                                 record["device_kind"])
    return 100.0 * floor / (sum(each) / 1e3)
