"""Percentiles over all scored rows of the live kind's two per-row clocks,
selected by `what`:

    detect_p50, detect_p95   end of the emit span of the tick that scored the
                             row - the row's due time on the generator's fixed
                             schedule (includes the wait for the snapshot and
                             any phase the loop has lost)
    score_p95                the same end - the snapshot that took the row"""


def read(record: dict, definition: dict):
    lat = record.get("row_latency_ms")
    return None if lat is None else lat[definition["what"]]
