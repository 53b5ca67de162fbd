"""A record of F fields a node on the SERVED path (ISSUE 43): a tiny
`node_preset(3)` fleet fed over a real localhost socket through
`TcpJsonlSource` -> `live_loop` -> `StreamGroupRegistry` -> `jit_chunk_step`
scores what the numpy oracle scores from the same `[T, G, 3]` rows — `null`
fields among them — and what `dispatch_chunk` scores when the rows are
replayed; two fields swapped on the wire, or a `null` delivered as 0.0, do
not."""

import time

import numpy as np
import pytest

from rtap_tpu.config import node_preset
from rtap_tpu.models.htm_model import oracle_record_step
from rtap_tpu.models.oracle.temporal_memory import TMOracle
from rtap_tpu.models.state import init_state
from rtap_tpu.service.loop import live_loop
from rtap_tpu.service.registry import StreamGroup, StreamGroupRegistry
from rtap_tpu.service.sources import TcpJsonlSource, send_jsonl

F, GROUPS, G, TICKS = 3, 2, 3, 6
SEED = 4_300_000_011  # beyond 2**31, like the driver's
TS0 = 2_000_000_000
IDS = [f"node{i:03d}" for i in range(GROUPS * G)]
PERM_LEAVES = ("perm", "syn_perm")


def offered_rows() -> np.ndarray:
    """[T, nodes, F]: a seeded shape a field; every tick one node has one
    `null` field, one node sends nothing at tick 2 (a missing sample), and
    fields 0 and 1 always differ by far more than a bucket."""
    rng = np.random.default_rng(SEED % (1 << 32))
    t = np.arange(TICKS)[:, None, None]
    base = np.array([20.0, 45.0, 70.0])[None, None, :]
    rows = (base + 6.0 * np.sin((t + rng.integers(0, 50, (1, len(IDS), F)))
                                / 3.0)
            + rng.normal(0, 1.0, (TICKS, len(IDS), F))).astype(np.float32)
    for k in range(TICKS):
        rows[k, k % len(IDS), (k + 1) % F] = np.nan
    rows[2, 4, :] = np.nan  # node004 is silent in slot 2
    return rows


def serve(rows: np.ndarray, native, on_wire=lambda values: values):
    """The rows through the real listener and the loop -> (raw [T, nodes],
    the registry, the source after the run). `on_wire` is what a faulty
    collector does to a record's values before sending them."""
    reg = StreamGroupRegistry(node_preset(F), group_size=G, backend="tpu",
                              seed=SEED)
    for sid in IDS:
        reg.add_stream(sid)
    reg.finalize()
    ids = reg.dispatch_ids()
    assert ids == IDS
    served = {g: [] for g in range(len(reg.groups))}
    for g, grp in enumerate(reg.groups):
        def collect(handle, inner=grp.collect_chunk, g=g):
            out = inner(handle)
            served[g].append(out[0].copy())
            return out
        grp.collect_chunk = collect
    src = TcpJsonlSource(ids, native=native, n_fields=F)

    def source(tick: int):
        records = [{"id": sid, "values": on_wire(rows[tick, i]),
                    "ts": TS0 + tick}
                   for i, sid in enumerate(ids)
                   if np.isfinite(rows[tick, i]).any()]
        before = src.records_parsed
        assert send_jsonl(src.address, records) == len(records)
        deadline = time.time() + 20
        while src.records_parsed < before + len(records):
            assert time.time() < deadline, "the listener lost records"
            time.sleep(0.002)
        return src(tick)

    with src:
        stats = live_loop(source, reg, n_ticks=TICKS, cadence_s=0.0)
    assert stats["ticks"] == TICKS and not stats.get("quarantined_groups")
    raw = np.concatenate([np.concatenate(served[g]) for g in served], axis=1)
    return raw, reg, src


def oracle(rows: np.ndarray):
    """rtap_tpu's numpy oracle over the same rows -> (raw [T, nodes],
    per-node state). Group g's streams start from seed + g, as the
    registry makes them."""
    cfg = node_preset(F)
    raw = np.zeros(rows.shape[:2], np.float32)
    states = []
    for i in range(rows.shape[1]):
        state = init_state(cfg, SEED + i // G)
        tm = TMOracle(state, cfg.tm)
        for k in range(rows.shape[0]):
            raw[k, i] = oracle_record_step(cfg, state, tm, rows[k, i],
                                           TS0 + k, True)
        states.append(state)
    return raw, states


@pytest.fixture(scope="module")
def rows():
    return offered_rows()


@pytest.fixture(scope="module")
def reference(rows):
    return oracle(rows)


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_served_equals_oracle_equals_replayed(rows, reference, native):
    raw, reg, src = serve(rows, native)
    ref_raw, ref_states = reference
    # (b) served = oracle: scores and permanences (exact on the CPU backend)
    assert np.abs(raw - ref_raw).max() <= 1e-6
    for i in range(len(IDS)):
        grp, slot = reg.groups[i // G], i % G
        for leaf in PERM_LEAVES:
            assert np.array_equal(np.asarray(grp.state[leaf][slot]),
                                  ref_states[i][leaf]), (i, leaf)
        assert int(np.asarray(grp.state["tm_overflow"]).sum()) == 0
    # (c) served = replayed: the same rows through dispatch_chunk, bit-equal
    ts = np.repeat((TS0 + np.arange(TICKS))[:, None], G, axis=1)
    for g in range(GROUPS):
        grp = StreamGroup(node_preset(F), IDS[g * G:(g + 1) * G],
                          seed=SEED + g, backend="tpu")
        replayed = np.concatenate([
            grp.run_chunk(rows[k:k + 1, g * G:(g + 1) * G], ts[k:k + 1])[0]
            for k in range(TICKS)])
        assert np.array_equal(replayed, raw[:, g * G:(g + 1) * G])
    # every offered record parsed once; nulls counted apart, the silent
    # node's slot and nothing else missing whole
    sent = int(np.isfinite(rows).any(axis=2).sum())
    assert (src.records_parsed, src.parse_errors, src.unknown_ids) == \
        (sent, 0, 0)
    assert src.values_null == TICKS and \
        src.values_parsed == sent * F - TICKS


@pytest.mark.parametrize("fault", ["fields_swapped", "null_as_zero"])
def test_a_faulty_collector_is_caught(rows, reference, fault):
    """(d) the comparison can fail: field order and `null` are part of the
    record, and a model fed otherwise scores otherwise."""
    def on_wire(values):
        values = values.copy()
        if fault == "fields_swapped":
            values[[0, 1]] = values[[1, 0]]
        else:
            values[~np.isfinite(values)] = 0.0
        return values

    raw, reg, _src = serve(rows, None, on_wire)
    ref_raw, ref_states = reference
    perm_gap = max(
        np.abs(np.asarray(reg.groups[i // G].state[leaf][i % G], np.float64)
               - ref_states[i][leaf].astype(np.float64)).max()
        for i in range(len(IDS)) for leaf in PERM_LEAVES)
    assert np.abs(raw - ref_raw).max() > 1e-6 or perm_gap > 0
