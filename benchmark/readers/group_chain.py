"""One chunk of one group from its enqueue to its fetch, through the device
(benchmark/device_clock.py:chain pairs them and places the device's line on
the host's clock). Selected by `what`, mean over the group-chunks of
`module`'s whole executions in the traced window, in ms:

    queue_ms   device start - enqueue end: the wait behind the programs
               enqueued before it (the live tick's group g waits for g)
    tail_ms    fetch end - max(device end, fetch start): what the blocking
               read costs once the scores exist — 0 for a round's quickest
               fetch, by which the device's clock is placed, so a lower
               bound by that one fetch's true tail

None — and a line saying why — where the counts do not match or a round
allows no offset; None, silently, for a trace with no such annotations."""

from benchmark import device_clock
from benchmark.scoped_trace import of_record


def read(record: dict, definition: dict):
    found = of_record(record)
    if found is None:
        return None
    triples = device_clock.of_record(record, definition["module"], found)
    if not triples:
        return None
    what = definition["what"]
    if what == "queue_ms":
        ns = [t["device_start"] - t["enqueue_end"] for t in triples]
    elif what == "tail_ms":
        ns = [t["fetch_end"] - max(t["device_end"], t["fetch_start"])
              for t in triples]
    else:
        raise ValueError(f"group_chain reader: unknown 'what' {what!r}")
    return sum(ns) / len(ns) / 1e6
