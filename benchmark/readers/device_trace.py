"""From the reduced profiler trace, selected by `what`:

    step_ms      device time of one execution of `module` (whole executions
                 inside the traced window) / ticks in it
    idle_share   100 * (1 - device busy / traced window)"""

from benchmark.trace_reduce import step_ms


def read(record: dict, definition: dict):
    trace = record.get("trace")
    if trace is None:
        return None
    if definition["what"] == "step_ms":
        return step_ms(trace, definition["module"], record["chunk_ticks"])
    if definition["what"] == "idle_share":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    raise ValueError(f"device_trace reader: unknown 'what' {definition['what']!r}")
