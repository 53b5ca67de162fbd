"""The serving loop's own clocks over the window's ticks (TraceRecorder
spans and the loop's stats), selected by `what` in the definition:

    host_ms        mean per tick of (tick span - its collect span)
    collect_ms     mean per tick of the collect span (the wait for the groups)
    missed_share   missed_deadlines / ticks, in %"""

import numpy as np


def read(record: dict, definition: dict):
    ticks = record.get("tick_spans")
    if not ticks:
        return None
    what = definition["what"]
    if what == "missed_share":
        stats = record["loop_stats"]
        return 100.0 * stats["missed_deadlines"] / max(1, stats["ticks"])
    collect = record["collect_spans"]
    if what == "collect_ms":
        return float(np.mean([d for _t, d in collect.values()]) * 1e3)
    if what == "host_ms":
        return float(np.mean([ticks[k][1] - collect.get(k, (0, 0.0))[1]
                              for k in ticks]) * 1e3)
    raise ValueError(f"loop_spans reader: unknown 'what' {what!r}")
