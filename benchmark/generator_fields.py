"""The live generator for records of F fields a node: benchmark/generator.py's
process — its schedule, its gate, its report — with payloads of its own.

    python -m benchmark.generator_fields --fields F --null-share P \
        <the arguments of benchmark.generator>

One JSONL record a node a slot, ``{"id": "<node>", "values": [v0, .., vF-1],
"ts": <s>}``; a metric the collector missed is ``null``. The offered set is a
pure function of the arguments (`offered_records`): every field a seeded
signal of its own, and `null_share` of the records, seeded, carry exactly one
``null`` field. Like benchmark/generator.py it never imports JAX or the
program; the measuring process (traffic kind `live_fields`) makes the same
set from the same arguments."""

from __future__ import annotations

import argparse
import functools
import json
import sys

import numpy as np

from benchmark import generator
from benchmark.feed import live_rows, make_sine_feed, seed_key, stream_ids

#: field f's Philox lane above the scalar mix's (benchmark/feed.py:live_rows)
_FIELD_LANE = 1 << 16


def offered_records(seed: int, n_nodes: int, n_slots: int, n_fields: int,
                    null_share: float, spread_s: float, quantum_s: float):
    """-> (values [n_slots, n_nodes, n_fields] f32 with NaN where the record
    carries ``null``, due offset phi [n_nodes] s, send offset [n_nodes] s).
    Node i's record of slot k is due at E + k * cadence + phi[i]: the scalar
    mix's schedule, one record where it has one row."""
    _one, phi, send = live_rows(seed, n_nodes, 1, spread_s, quantum_s)
    values = np.stack([
        make_sine_feed(n_nodes, n_slots,
                       seed_key(seed, 0x11FE + f * _FIELD_LANE))[0]
        for f in range(n_fields)], axis=-1)
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x0711)))
    n_null = int(null_share * n_slots * n_nodes)
    records = rng.choice(n_slots * n_nodes, size=n_null, replace=False)
    values.reshape(-1, n_fields)[records, rng.integers(0, n_fields, n_null)] \
        = np.nan
    return values, phi, send


def build_payloads(seed: int, n_nodes: int, n_slots: int, spread: float,
                   quantum: float, ts_base: int, n_fields: int,
                   null_share: float):
    """benchmark/generator.py:build_payloads for vector records: the same
    batches at the same offsets, a record where it has a row."""
    values, phi, send = offered_records(seed, n_nodes, n_slots, n_fields,
                                        null_share, spread, quantum)
    offsets, batch_of = np.unique(send, return_inverse=True)
    order = np.argsort(batch_of, kind="stable")
    bounds = np.searchsorted(batch_of[order], np.arange(len(offsets) + 1))
    prefixes = [f'{{"id": "{sid}", "values": [' for sid in stream_ids(n_nodes)]
    payloads = []
    for k in range(n_slots):
        suffix = f'], "ts": {ts_base + k}}}\n'
        # one dump a slot: "[[a, b, c], [d, e, f], ...]" -> a list a record
        lists = json.dumps(values[k].astype(float).tolist())[2:-2] \
            .replace("NaN", "null").split("], [")
        lines = [prefixes[i] + lists[i] + suffix for i in order]
        payloads.append([
            "".join(lines[bounds[b]:bounds[b + 1]]).encode()
            for b in range(len(offsets))])
    return offsets, payloads, np.diff(bounds), phi, batch_of


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fields", type=int, required=True)
    ap.add_argument("--null-share", type=float, required=True)
    a, rest = ap.parse_known_args(argv)
    # the scalar generator's main, sending this module's payloads
    generator.build_payloads = functools.partial(
        build_payloads, n_fields=a.fields, null_share=a.null_share)
    return generator.main(rest)


if __name__ == "__main__":
    sys.exit(main())
