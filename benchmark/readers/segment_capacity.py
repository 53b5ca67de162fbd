"""Cells OF THE SAMPLED STREAMS (the ones `correct` follows: the first, the
last and the configuration's seeded draw — 3 of nab-2048's 17, not the
group) whose every segment slot is in use after the run, counted by the
program's own counter function
(rtap_tpu/service/registry.py:segment_capacity, the one behind
StreamGroup.capacity_stats): 0 means `max_segments_per_cell` — cut from
NuPIC's 128 — was never the limit on those streams, so none of their
segments was evicted for want of a slot. The replay kind's record carries
state rows of the sampled streams only and the groups are gone when readers
run; the whole group's count travels in the record once a `benchmark` issue
puts it beside `tm_overflow` (PERF.md s7). Counted off the timed path, from
the permanence rows the run already fetched for `correct` (a slot is in use
iff it holds a synapse: synapses die at permanence 0 and an empty segment
is freed in the same sweep). A program without the counter gives nothing to
read."""

import numpy as np


def read(record: dict, definition: dict):
    try:
        from rtap_tpu.service.registry import segment_capacity
    except ImportError:
        return None
    rows = [s["syn_perm"] for s in record.get("sample", ())
            if "syn_perm" in s]
    if not rows:
        return None
    in_use = np.stack([(np.asarray(r) > 0).any(-1) for r in rows])
    return segment_capacity(in_use)[definition["what"]]
