"""The fused step's share of its memory roofline, in %: the least time one
tick of one group can take (its state read once and written once at the
chip's peak HBM rate; bytes from shapes, benchmark/roofline.py) over the
device time the tick took (benchmark/trace_reduce.py:step_ms)."""

from benchmark.roofline import step_floor_seconds
from benchmark.trace_reduce import step_ms


def read(record: dict, definition: dict):
    if record.get("trace") is None:
        return None
    ms = step_ms(record["trace"], definition["module"], record["chunk_ticks"])
    if ms is None:
        return None
    floor = step_floor_seconds(record["config"]["model"],
                               record["config"]["layout"]["group_size"],
                               record["device_kind"])
    return 100.0 * floor / (ms / 1e3)
