"""The live generator for a service-structured fleet one of whose services
drifts and then cascades: the records of benchmark/generator_fields.py, every
node named for its service, a precursor ramp and a node-by-node fault in them.

    python -m benchmark.generator_cascade --fields F --null-share P \
        --history H --nodes-per-service M --signal '<the traffic file's
        "signal" object>' --cascade '<its "cascade" object>' \
        <the arguments of benchmark.generator>

The offered set is a pure function of the arguments (`offered_cascade`): node
i is node ``i % M`` of service ``i // M`` (`node_ids`: ``svc<sss>-<nn>``, the
repo's inference-friendly naming, a dotless id its own node); every field of
every node is one seeded signal from the first tick of its history to the last
slot of the window — a diurnal sine with the node's own phase plus AR(1) noise
(the history is what the fleet's models were warmed on offline, predictor
armed; only the window goes on the wire). `services` of the services, seeded,
cascade: the origin node (seeded) ramps all its fields linearly from 0 to
`ramp_units` over `ramp_slots` slots from a slot drawn in `ramp_start_slots`,
then takes the fault's levels for `fault_slots` slots; each of the next
`downstream_nodes` nodes of the service takes the same fault `cascade_lag`
slots after the one before it. Every other node stays healthy (the
false-precursor control); no node is killed, every slot is offered;
`null_share` of the window's records carry one ``null`` field. Like
benchmark/generator.py it never imports JAX or the program; the measuring
process (traffic kind `live_predictive`) makes the same set from the same
arguments."""

from __future__ import annotations

import argparse
import functools
import json
import sys

import numpy as np

from benchmark import generator
from benchmark.feed import live_rows, seed_key
from benchmark.generator_fields import _FIELD_LANE


def node_ids(n_nodes: int, nodes_per_service: int) -> list[str]:
    """The ids the fleet's nodes carry on the wire, in the registry and in
    the topology spec: node i = node i % M of service i // M."""
    return [f"svc{i // nodes_per_service:03d}-{i % nodes_per_service:02d}"
            for i in range(n_nodes)]


def topology_spec(n_nodes: int, nodes_per_service: int) -> dict:
    """The spec `serve --topology` takes (correlate/topology.py:from_spec):
    every service a cluster of its own, no links between services."""
    ids = node_ids(n_nodes, nodes_per_service)
    services: dict[str, list[str]] = {}
    for sid in ids:
        services.setdefault(sid.partition("-")[0], []).append(sid)
    return {"services": services, "links": []}


def draw_cascades(seed: int, n_nodes: int, n_slots: int,
                  nodes_per_service: int, cascade: dict) -> list[dict]:
    """-> one dict a cascaded service, by service: {service, origin (node
    index), ramp (first slot, slot after the last), faults: [(node index,
    first slot, slot after the last)] — the origin's first, then the
    downstream nodes' in cascade order}. Slots past the window are cut."""
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0xCA5C)))
    n_services = n_nodes // nodes_per_service
    n = min(n_services, cascade["services"])
    services = np.sort(rng.choice(n_services, size=n, replace=False))
    origins = rng.integers(0, nodes_per_service, size=n)
    lo, _, hi = cascade["ramp_start_slots"].partition("-")
    starts = rng.integers(int(lo), int(hi) + 1, size=n)
    drawn = []
    for svc, origin, r0 in zip(services, origins, starts):
        onset = int(r0) + cascade["ramp_slots"]
        faults = []
        for j in range(1 + min(cascade["downstream_nodes"],
                               nodes_per_service - 1)):
            t0 = onset + j * cascade["cascade_lag"]
            node = int(svc) * nodes_per_service \
                + (int(origin) + j) % nodes_per_service
            faults.append((node, min(n_slots, t0),
                           min(n_slots, t0 + cascade["fault_slots"])))
        drawn.append({"service": int(svc), "origin": faults[0][0],
                      "ramp": (min(n_slots, int(r0)), min(n_slots, onset)),
                      "faults": faults})
    return drawn


def offered_cascade(seed: int, n_nodes: int, n_slots: int, n_fields: int,
                    null_share: float, spread_s: float, quantum_s: float,
                    history: int, nodes_per_service: int, signal: dict,
                    cascade: dict):
    """-> (history rows [history, n_nodes, n_fields] f32, window rows
    [n_slots, n_nodes, n_fields] f32 with NaN where a record carries
    ``null``, due offset phi [n_nodes] s, send offset [n_nodes] s, the
    cascades drawn). Node i's record of slot k is due at
    E + k * cadence + phi[i]."""
    _one, phi, send = live_rows(seed, n_nodes, 1, spread_s, quantum_s)
    T = history + n_slots
    fields = []
    for f in range(n_fields):
        rng = np.random.Generator(np.random.Philox(
            key=seed_key(seed, 0x11FE + f * _FIELD_LANE)))
        phase = rng.integers(0, int(signal["period_s"]), n_nodes)
        t_idx = np.arange(T)[:, None]
        base = signal["level"] + signal["amplitude"] * np.sin(
            2 * np.pi * (t_idx + phase[None, :]) / signal["period_s"])
        # AR(1) noise, started at its stationary spread
        innov = rng.normal(0, signal["noise_sigma"], (T, n_nodes))
        phi_n = signal["noise_phi"]
        noise = np.empty((T, n_nodes))
        noise[0] = innov[0] / np.sqrt(1 - phi_n ** 2)
        for t in range(1, T):
            noise[t] = phi_n * noise[t - 1] + innov[t]
        fields.append((base + noise).astype(np.float32))
    full = np.stack(fields, axis=-1)
    past, values = full[:history], full[history:].copy()
    drawn = draw_cascades(seed, n_nodes, n_slots, nodes_per_service, cascade)
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0xCA5D)))
    for c in drawn:
        r0, r1 = c["ramp"]
        steps = np.arange(r1 - r0, dtype=np.float32)
        values[r0:r1, c["origin"], :] += (
            cascade["ramp_units"] * steps / cascade["ramp_slots"])[:, None]
        for node, t0, t1 in c["faults"]:
            for field, level, sigma in cascade["fault_fields"]:
                values[t0:t1, node, field] = (
                    level + rng.normal(0, sigma, max(0, t1 - t0)))
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x0711)))
    n_null = int(null_share * n_slots * n_nodes)
    records = rng.choice(n_slots * n_nodes, size=n_null, replace=False)
    values.reshape(-1, n_fields)[records, rng.integers(0, n_fields, n_null)] \
        = np.nan
    return past, values, phi, send, drawn


def build_payloads(seed: int, n_nodes: int, n_slots: int, spread: float,
                   quantum: float, ts_base: int, n_fields: int,
                   null_share: float, history: int, nodes_per_service: int,
                   signal: dict, cascade: dict):
    """benchmark/generator_fields.py:build_payloads over this fleet's
    records and ids: the same batches at the same offsets."""
    _past, values, phi, send, _drawn = offered_cascade(
        seed, n_nodes, n_slots, n_fields, null_share, spread, quantum,
        history, nodes_per_service, signal, cascade)
    offsets, batch_of = np.unique(send, return_inverse=True)
    order = np.argsort(batch_of, kind="stable")
    bounds = np.searchsorted(batch_of[order], np.arange(len(offsets) + 1))
    prefixes = [f'{{"id": "{sid}", "values": ['
                for sid in node_ids(n_nodes, nodes_per_service)]
    payloads = []
    for k in range(n_slots):
        suffix = f'], "ts": {ts_base + k}}}\n'
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
    ap.add_argument("--history", type=int, required=True)
    ap.add_argument("--nodes-per-service", type=int, required=True)
    ap.add_argument("--signal", type=json.loads, required=True)
    ap.add_argument("--cascade", type=json.loads, required=True)
    a, rest = ap.parse_known_args(argv)
    # the scalar generator's main, sending this module's payloads
    generator.build_payloads = functools.partial(
        build_payloads, n_fields=a.fields, null_share=a.null_share,
        history=a.history, nodes_per_service=a.nodes_per_service,
        signal=a.signal, cascade=a.cascade)
    return generator.main(rest)


if __name__ == "__main__":
    sys.exit(main())
