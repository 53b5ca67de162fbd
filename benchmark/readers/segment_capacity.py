"""Cells of ALL resident streams whose every segment slot is in use at the
end of the run, by the program's own counter
(rtap_tpu/service/registry.py:segment_capacity behind
StreamGroup.capacity_stats, summed over the groups by the replay kind after
the window, beside `tm_overflow`): 0 means `max_segments_per_cell` — cut
from NuPIC's 128 — was never the limit on any stream, so no segment was
evicted for want of a slot. Counted off the timed path. A record without
the count (another kind, a program without the counter) gives nothing to
read."""


def read(record: dict, definition: dict):
    return record.get("tm_capacity", {}).get(definition["what"])
