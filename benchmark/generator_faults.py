"""The live generator for a fleet whose nodes degrade and die: the records of
benchmark/generator_fields.py with the upstream system's three faults in them.

    python -m benchmark.generator_faults --fields F --null-share P \
        --history H --faults '<the traffic file's "faults" object>' \
        <the arguments of benchmark.generator>

The offered set is a pure function of the arguments (`offered_fleet`): every
node's every field is one seeded signal from the first tick of its history to
the last slot of the window (the history is what the fleet's models were
warmed on offline; only the window goes on the wire), `fault_node_share` of
the nodes, seeded, take one fault each — a field pinned to a stressed level
with its own noise (cpu_stress, net_loss), or no record at all for the
duration (node_kill: the node is gone and comes back with its signal) — and
`null_share` of the window's records carry one ``null`` field. Like
benchmark/generator.py it never imports JAX or the program; the measuring
process (traffic kind `live_resumed`) makes the same set from the same
arguments."""

from __future__ import annotations

import argparse
import functools
import json
import sys

import numpy as np

from benchmark import generator
from benchmark.feed import live_rows, make_sine_feed, seed_key, stream_ids
from benchmark.generator_fields import _FIELD_LANE


def draw_faults(seed: int, n_nodes: int, n_slots: int, faults: dict) -> list:
    """-> [(node, kind, first slot, slot after the last)], by node: which
    nodes are hit, by what and when. Kinds are dealt in equal shares."""
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x0FA7)))
    n = min(n_nodes, round(faults["fault_node_share"] * n_nodes))
    nodes = np.sort(rng.choice(n_nodes, size=n, replace=False))
    kinds = sorted(faults["fault_kinds"])
    dealt = rng.permutation(n) % len(kinds)
    lo, _, hi = faults["fault_onset_slots"].partition("-")
    onset = rng.integers(int(lo), int(hi) + 1, size=n)
    return [(int(node), kinds[k], int(t0),
             min(n_slots, int(t0) + faults["fault_duration_slots"]))
            for node, k, t0 in zip(nodes, dealt, onset)]


def offered_fleet(seed: int, n_nodes: int, n_slots: int, n_fields: int,
                  null_share: float, spread_s: float, quantum_s: float,
                  history: int, faults: dict):
    """-> (history rows [history, n_nodes, n_fields] f32, window rows
    [n_slots, n_nodes, n_fields] f32 with NaN where a record carries
    ``null`` or was never offered, offered [n_slots, n_nodes] bool, due
    offset phi [n_nodes] s, send offset [n_nodes] s, the faults drawn).
    Node i's record of slot k is due at E + k * cadence + phi[i]."""
    _one, phi, send = live_rows(seed, n_nodes, 1, spread_s, quantum_s)
    signal = np.stack([
        make_sine_feed(n_nodes, history + n_slots,
                       seed_key(seed, 0x11FE + f * _FIELD_LANE))[0]
        for f in range(n_fields)], axis=-1)
    past, values = signal[:history], signal[history:].copy()
    drawn = draw_faults(seed, n_nodes, n_slots, faults)
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x0FA8)))
    offered = np.ones((n_slots, n_nodes), bool)
    for node, kind, t0, t1 in drawn:
        how = faults["fault_kinds"][kind]
        if how.get("kill"):
            offered[t0:t1, node] = False
        for field, level, sigma in how.get("fields", ()):
            values[t0:t1, node, field] = (
                level + rng.normal(0, sigma, max(0, t1 - t0)))
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x0711)))
    n_null = int(null_share * n_slots * n_nodes)
    records = rng.choice(n_slots * n_nodes, size=n_null, replace=False)
    values.reshape(-1, n_fields)[records, rng.integers(0, n_fields, n_null)] \
        = np.nan
    values[~offered] = np.nan
    return past, values, offered, phi, send, drawn


def build_payloads(seed: int, n_nodes: int, n_slots: int, spread: float,
                   quantum: float, ts_base: int, n_fields: int,
                   null_share: float, history: int, faults: dict):
    """benchmark/generator_fields.py:build_payloads with the records that
    were never offered left out: the same batches at the same offsets, and
    the row count of every batch of every slot ([n_slots, B]: a killed node
    sends nothing)."""
    _past, values, offered, phi, send, _drawn = offered_fleet(
        seed, n_nodes, n_slots, n_fields, null_share, spread, quantum,
        history, faults)
    offsets, batch_of = np.unique(send, return_inverse=True)
    order = np.argsort(batch_of, kind="stable")
    bounds = np.searchsorted(batch_of[order], np.arange(len(offsets) + 1))
    prefixes = [f'{{"id": "{sid}", "values": [' for sid in stream_ids(n_nodes)]
    payloads = []
    for k in range(n_slots):
        suffix = f'], "ts": {ts_base + k}}}\n'
        lists = json.dumps(values[k].astype(float).tolist())[2:-2] \
            .replace("NaN", "null").split("], [")
        lines = [prefixes[i] + lists[i] + suffix if offered[k, i] else ""
                 for i in order]
        payloads.append([
            "".join(lines[bounds[b]:bounds[b + 1]]).encode()
            for b in range(len(offsets))])
    rows = np.add.reduceat(offered[:, order].astype(np.int64), bounds[:-1],
                           axis=1)
    return offsets, payloads, rows, phi, batch_of


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fields", type=int, required=True)
    ap.add_argument("--null-share", type=float, required=True)
    ap.add_argument("--history", type=int, required=True)
    ap.add_argument("--faults", type=json.loads, required=True)
    a, rest = ap.parse_known_args(argv)
    # the scalar generator's main, sending this module's payloads
    generator.build_payloads = functools.partial(
        build_payloads, n_fields=a.fields, null_share=a.null_share,
        history=a.history, faults=a.faults)
    return generator.main(rest)


if __name__ == "__main__":
    sys.exit(main())
