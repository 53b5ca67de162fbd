"""Seconds of one of the benchmark's own set-up spans (`span` in the
definition), e.g. the warm-up of the cell's programs."""


def read(record: dict, definition: dict):
    span = record["bench_spans"].get(definition["span"])
    return None if span is None else span[1]
