"""Host self time per chunk in the stream group's dispatch_chunk and
collect_chunk, outside the blocking wait for the device (staging, enqueue,
fetch, host likelihood), mean over the window's chunks, in ms."""

import numpy as np


def read(record: dict, definition: dict):
    host = record.get("host_s_per_chunk")
    return None if not host else float(np.mean(host) * 1e3)
