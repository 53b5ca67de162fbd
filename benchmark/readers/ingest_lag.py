"""Median over slots of: the slot's last batch handed to the socket -> every
row of the slot counted by the listener's parser (`records_parsed` reaching
the slot's cumulative count, polled every 2 ms beside the loop)."""

import numpy as np


def read(record: dict, definition: dict):
    report, parsed = record.get("generator"), record.get("parsed_at")
    if report is None or parsed is None:
        return None
    lag = np.asarray(parsed) - np.asarray(report["last_send"])
    lag = lag[np.isfinite(lag)]
    return None if not len(lag) else float(np.median(lag) * 1e3)
