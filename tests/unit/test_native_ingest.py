"""Native JSONL ingest parser (rtap_tpu/native/jsonl_parser.c) vs the pure
Python handler: counter-for-counter, value-for-value parity on the realistic
record space, plus the C-only mechanics (chunk splits, remainder flush,
oversized-line resync).

The native path exists because the host core feeding the chip at the 100k
streams/s north star cannot spend microseconds per record in json.loads
(SURVEY.md C18, §7 host-feed hard part); parity here is what lets the
service swap it in by default with the Python path as fallback.
"""

import json
import socket
import time

import numpy as np
import pytest

from rtap_tpu.service.sources import TcpJsonlSource, send_jsonl

try:
    from rtap_tpu.native import NativeJsonlState

    _err = None
except Exception as e:  # no toolchain: the fallback story, not a failure
    NativeJsonlState = None
    _err = e

needs_native = pytest.mark.skipif(
    NativeJsonlState is None, reason=f"native build unavailable: {_err}")

IDS = ["node0000.m0", "node0000.m1", "a", "long." * 10 + "id"]


def _state(ids=IDS):
    latest = np.full(len(ids), np.nan, np.float32)
    st = NativeJsonlState(ids, latest)
    return st, latest


# ------------------------------------------------------------ direct C API


@needs_native
def test_split_chunks_and_flush():
    st, latest = _state()
    c = st.new_conn()
    c.feed(b'{"id": "node0000.m0", "va')
    c.feed(b'lue": 2.5, "ts": 7}\n{"id": "a", "value"')
    c.feed(b': -1}\n{"id": "node0000.m1", "value": 9}')  # no trailing \n
    assert np.isnan(latest[1])  # unterminated: not yet processed
    c.flush()                   # EOF processes it, like rfile iteration
    assert latest[0] == np.float32(2.5)
    assert latest[1] == np.float32(9)
    assert latest[2] == np.float32(-1)
    assert st.ts_buf[0] == 7
    assert list(st.counters) == [3, 0, 0]
    c.close()


@needs_native
def test_value_and_ts_coercions_match_python():
    st, latest = _state()
    c = st.new_conn()
    # every coercion np.float32/int accept: quoted numbers, bools,
    # scientific notation, float ts (truncates), quoted ts digits
    c.feed(b'{"id": "a", "value": "7.25", "ts": 101.9}\n')
    assert latest[2] == np.float32(7.25) and st.ts_buf[0] == 101
    c.feed(b'{"id": "a", "value": true, "ts": "144"}\n')
    assert latest[2] == np.float32(1.0) and st.ts_buf[0] == 144
    c.feed(b'{"id": "a", "value": -3e2}\n')
    assert latest[2] == np.float32(-300.0)
    # np.float32(None) is nan, NOT an error: null values are missing samples
    c.feed(b'{"id": "a", "value": null}\n')
    assert np.isnan(latest[2])
    assert list(st.counters) == [4, 0, 0]
    # ...but np.float32("null") (quoted) raises
    c.feed(b'{"id": "a", "value": "null"}\n')
    assert list(st.counters) == [4, 1, 0]
    # bad ts on a known id still applies the value first (Python assigns
    # latest[i] before int(ts) can raise)
    c.feed(b'{"id": "a", "value": 5, "ts": "xx"}\n')
    assert latest[2] == np.float32(5.0)
    assert list(st.counters) == [4, 2, 0]
    # quoted ts goes through int(str): "101.9" and "1e3" raise in Python
    # (value still applied); hex never parses as a value
    c.feed(b'{"id": "a", "value": 6, "ts": "101.9"}\n')
    assert latest[2] == np.float32(6.0)
    c.feed(b'{"id": "a", "value": 8, "ts": "1e3"}\n')
    c.feed(b'{"id": "a", "value": "0x10"}\n')  # np.float32("0x10") raises
    assert list(st.counters) == [4, 5, 0]
    assert st.ts_buf[0] == 144  # unchanged by the failed conversions
    c.feed(b'{"id": "a", "value": 7, "ts": " -12 "}\n')  # int(" -12 ") works
    assert list(st.counters) == [5, 5, 0]
    c.close()


@needs_native
def test_counter_semantics_match_python_ordering():
    st, latest = _state()
    c = st.new_conn()
    c.feed(b'{"value": 5}\n')            # no id -> rec["id"] KeyError
    c.feed(b'{"id": "a"}\n')             # known id, no value -> KeyError
    c.feed(b'{"id": "zzz"}\n')           # unknown id checked BEFORE value
    c.feed(b'{"id": 5, "value": 1}\n')   # non-string id -> dict.get miss
    c.feed(b'garbage\n\n')               # malformed + empty line
    assert list(st.counters) == [0, 4, 2]
    # unhashable id: Python's dict.get({...}) raises TypeError -> error,
    # NOT unknown (scalar non-string ids are hashable and count unknown)
    c.feed(b'{"id": {"x": 1}, "value": 2}\n{"id": [1], "value": 2}\n')
    assert list(st.counters) == [0, 6, 2]
    c.close()


@needs_native
def test_oversized_line_resync():
    st, latest = _state()
    c = st.new_conn()
    big = b'{"id": "a", "value": ' + b"9" * 70000  # > MAX_LINE, no newline yet
    c.feed(big)
    c.feed(b'999}\n{"id": "a", "value": 3}\n')
    assert list(st.counters) == [1, 1, 0]  # oversized -> 1 error, then resync
    assert latest[2] == np.float32(3.0)
    c.close()


@needs_native
def test_escaped_strings_and_nested_values():
    st, latest = _state()
    c = st.new_conn()
    # escaped quote inside an irrelevant field; nested object skipped
    c.feed(b'{"note": "q\\"uoted", "id": "a", "meta": {"x": [1, 2]}, "value": 4}\n')
    assert latest[2] == np.float32(4.0)
    assert list(st.counters) == [1, 0, 0]
    c.close()


# ----------------------------------------------------- socket-level parity


def _drive(native: bool) -> tuple[np.ndarray, int, int, int]:
    ids = [f"s{i}" for i in range(8)]
    recs = []
    rng = np.random.default_rng(7)
    for k in range(500):
        recs.append({"id": ids[int(rng.integers(0, 8))],
                     "value": float(rng.normal()), "ts": 1700000000 + k})
    recs.insert(50, {"id": "nope", "value": 1.0})            # unknown
    recs.insert(90, {"id": ids[0], "value": "not-a-number"})  # parse error
    # in-order sentinel LAST: seeing its value means every record on this
    # connection was processed — counters alone are satisfied at record ~91
    # and would let the drain race the rest of the stream
    recs.append({"id": ids[7], "value": 424242.0, "ts": 1700009999})
    src = TcpJsonlSource(ids, native=native)
    with src:
        assert src.native_active == native
        send_jsonl(src.address, recs)
        deadline = time.time() + 5
        while time.time() < deadline:
            with src._lock:
                if src._latest[7] == np.float32(424242.0):
                    break
            time.sleep(0.02)
        values, ts = src(0)
    return values, ts, src.parse_errors, src.unknown_ids, src.records_parsed


@needs_native
def test_socket_parity_native_vs_python():
    v_n, ts_n, pe_n, unk_n, rec_n = _drive(native=True)
    v_p, ts_p, pe_p, unk_p, rec_p = _drive(native=False)
    assert np.array_equal(v_n, v_p, equal_nan=True)
    assert (ts_n, pe_n, unk_n) == (ts_p, pe_p, unk_p) == (ts_p, 1, 1)
    # ISSUE 7 satellite: success counting must agree across parser
    # backends (the Python fallback used to return None and starve
    # rtap_obs_ingest_records_total)
    assert rec_n == rec_p == 501


@needs_native
def test_multi_connection_and_drain():
    ids = ["x", "y"]
    src = TcpJsonlSource(ids, native=True)
    with src:
        send_jsonl(src.address, [{"id": "x", "value": 1.0, "ts": 10}])
        send_jsonl(src.address, [{"id": "y", "value": 2.0, "ts": 12}])
        deadline = time.time() + 5
        while time.time() < deadline and src.records_parsed != 2:
            time.sleep(0.02)
        assert src.records_parsed == 2
        values, ts = src(0)
        assert values[0] == 1.0 and values[1] == 2.0 and ts == 12
        # drain: next tick with no pushes is all-NaN, ts sticks
        values2, ts2 = src(1)
        assert np.isnan(values2).all() and ts2 == 12


def _fuzz_records(seed: int, ids: list[str], n: int) -> list[bytes]:
    """Randomized realistic-space records: shuffled field order, mixed
    value/ts types (including the coercible and the erroneous), unknown
    ids, extra fields, whitespace variation, malformed tails."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        r = rng.random()
        sid = ids[int(rng.integers(0, len(ids)))] if r < 0.85 else "ghost"
        value = rng.choice([
            str(float(rng.normal())), '"7.5"', "true", "false", "null",
            '"nope"', str(int(rng.integers(-100, 100))), "1e3",
        ])
        ts = rng.choice([str(int(rng.integers(1, 10**9))), '"123"',
                         '"9.5"', "55.7", "null"])
        fields = [f'"id": "{sid}"', f'"value": {value}', f'"ts": {ts}',
                  '"extra": {"nested": [1, "x"]}']
        rng.shuffle(fields)
        sep = ", " if rng.random() < 0.8 else ","
        line = "{" + sep.join(fields) + "}"
        if rng.random() < 0.06:
            line = line[: int(rng.integers(1, len(line)))]  # malformed tail
        out.append(line.encode())
    return out


@needs_native
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_socket_parity_fuzz(seed):
    """Native and Python paths must agree value-for-value and counter-for-
    counter across the randomized realistic record space — the evidence
    behind swapping the native parser in by default."""
    ids = [f"n{i}" for i in range(6)]
    lines = _fuzz_records(seed, ids, 400)
    payload = b"\n".join(lines) + b"\n"
    sentinel = json.dumps({"id": ids[0], "value": 31337.0}).encode() + b"\n"
    results = []
    for native in (True, False):
        src = TcpJsonlSource(ids, native=native)
        with src:
            with socket.create_connection(src.address, timeout=5.0) as s:
                s.sendall(payload + sentinel)
            deadline = time.time() + 10
            while time.time() < deadline:
                with src._lock:
                    if src._latest[0] == np.float32(31337.0):
                        break
                time.sleep(0.01)
            values, ts = src(0)
        results.append((values, ts, src.parse_errors, src.unknown_ids,
                        src.records_parsed))
    (v_n, ts_n, pe_n, unk_n, rec_n), (v_p, ts_p, pe_p, unk_p, rec_p) \
        = results
    assert np.array_equal(v_n, v_p, equal_nan=True)
    assert (ts_n, pe_n, unk_n, rec_n) == (ts_p, pe_p, unk_p, rec_p)
    assert pe_n > 0 and unk_n > 0  # the fuzz actually exercised both paths
    assert rec_n > 0  # and the success counter, on BOTH backends


@needs_native
def test_concurrent_producers_stress():
    """Two live connections pushing interleaved records in tiny odd-sized
    socket writes: per-connection remainder isolation plus the shared
    output array under the chunk lock. Every stream must end at its
    producer's final value and no record may be miscounted."""
    import threading

    G = 32
    ids = [f"c{i}" for i in range(G)]
    src = TcpJsonlSource(ids, native=True)
    n_each = 400

    def produce(half: int):
        own = ids[half * (G // 2):(half + 1) * (G // 2)]
        with socket.create_connection(src.address, timeout=5.0) as s:
            payload = b"".join(
                json.dumps({"id": own[k % len(own)],
                            "value": half * 1000.0 + k,
                            "ts": 1700000000 + k}).encode() + b"\n"
                for k in range(n_each)
            )
            # deliberately awkward write sizes to force mid-record splits
            for off in range(0, len(payload), 17):
                s.sendall(payload[off:off + 17])

    with src:
        threads = [threading.Thread(target=produce, args=(h,)) for h in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        deadline = time.time() + 10
        while time.time() < deadline and src.records_parsed < 2 * n_each:
            time.sleep(0.02)
        assert src.records_parsed == 2 * n_each
        assert src.parse_errors == 0 and src.unknown_ids == 0
        values, ts = src(0)
    # last value per stream: producer h wrote k = i, i+16, ... for its
    # stream i; the final write for stream i is the largest such k
    for half in (0, 1):
        own = list(range(half * (G // 2), (half + 1) * (G // 2)))
        for j, g in enumerate(own):
            last_k = max(k for k in range(n_each) if k % len(own) == j)
            assert values[g] == np.float32(half * 1000.0 + last_k)
    assert ts == 1700000000 + n_each - 1


def test_python_fallback_forced():
    src = TcpJsonlSource(["x"], native=False)
    with src:
        assert not src.native_active
        send_jsonl(src.address, [{"id": "x", "value": 3.5, "ts": 9}])
        deadline = time.time() + 5
        while time.time() < deadline:
            with src._lock:
                if not np.isnan(src._latest[0]):
                    break
            time.sleep(0.02)
        values, ts = src(0)
    assert values[0] == np.float32(3.5) and ts == 9
    assert src.records_parsed == 1  # counted on the fallback path too


def test_python_fallback_bad_ts_keeps_value_not_counted():
    """The C parser's ordering rule on the Python path: a bad ts keeps
    the value (written first) but the record counts as a parse error,
    never a parsed success — backends must agree on BOTH tallies."""
    src = TcpJsonlSource(["x"], native=False)
    with src:
        send_jsonl(src.address, [{"id": "x", "value": 5, "ts": "xx"}])
        deadline = time.time() + 5
        while time.time() < deadline and src.parse_errors < 1:
            time.sleep(0.02)
        values, _ = src(0)
    assert values[0] == np.float32(5.0)
    assert src.records_parsed == 0 and src.parse_errors == 1


@needs_native
def test_native_unknown_name_capture():
    """track_unknown on the NATIVE path: the C parser captures unknown-id
    names into the bounded buffer and drain_unknown returns them — serve
    --auto-register no longer needs the Python parse path."""
    src = TcpJsonlSource(["a", "b"], port=0, native=True,
                         track_unknown=True).start()
    try:
        assert src.native_active
        # the escaped id rides raw: wire bytes 'café' — capture must
        # SKIP it (a name registered under its wire spelling would
        # dead-letter on the Python fallback path, which json-decodes)
        with socket.create_connection(src.address, timeout=5.0) as s:
            s.sendall(b'{"id": "caf\\u00e9", "value": 0.5}\n')
        send_jsonl(src.address, [
            {"id": "a", "value": 1.0},
            {"id": "newcomer.x", "value": 2.0},
            {"id": "newcomer.y", "value": 3.0},
            {"id": "newcomer.x", "value": 4.0},  # dup: set dedups
            {"id": 123, "value": 5.0},           # numeric id: counted, not captured
        ])
        # both connections' handlers are async: wait for ALL 5 unknown
        # RECORDS (escaped café, x twice, y, numeric 123 — hashable miss
        # like dict.get(5)) before draining the captured names
        deadline = time.time() + 5
        while time.time() < deadline and src.unknown_ids < 5:
            time.sleep(0.02)
        assert src.unknown_ids == 5
        # only the 2 distinct plain string NAMES are capturable
        assert src.drain_unknown() == ["newcomer.x", "newcomer.y"]
        assert src.drain_unknown() == []  # drained
    finally:
        src.close()


@needs_native
def test_native_set_ids_swaps_table_mid_connection():
    """set_ids on the native path: the owner's table swap propagates to a
    per-connection parser mid-stream (shared indirection), partial-line
    state survives, and retained ids keep their latest value by id."""
    src = TcpJsonlSource(["a", "b"], port=0, native=True,
                         track_unknown=True).start()
    try:
        with socket.create_connection(src.address, timeout=5.0) as s:
            s.sendall(b'{"id": "a", "value": 7.0}\n{"id": "c", "value"')
            deadline = time.time() + 5
            while time.time() < deadline:
                with src._lock:
                    if src._latest[0] == np.float32(7.0):
                        break
                time.sleep(0.02)
            # membership change while the connection holds a partial line
            src.set_ids(["c", "a"])  # new id first: order is the caller's
            s.sendall(b": 9.0}\n")
        deadline = time.time() + 5
        while time.time() < deadline:
            with src._lock:
                if src._latest[0] == np.float32(9.0):
                    break
            time.sleep(0.02)
        values, _ = src(0)
        assert values[0] == np.float32(9.0)   # c: completed after the swap
        assert values[1] == np.float32(7.0)   # a: carried over BY ID
    finally:
        src.close()


def test_python_fallback_parse_error_count_is_exact_under_concurrency():
    """rtap-lint race-pass fix (ISSUE 12): the Python fallback handler
    bumped ``_py_parse_errors`` OUTSIDE the chunk lock — one
    read-modify-write per malformed line across N concurrent producer
    threads loses increments (the classic += lost update; every other
    tally already sat under the lock). The fix moves the bump under
    the lock; this pins the count exact across concurrent garbage
    producers on the fallback path."""
    import sys
    import threading

    src = TcpJsonlSource(["a", "b"], native=False)
    n_threads, n_bad = 6, 250

    def produce():
        with socket.create_connection(src.address, timeout=5.0) as s:
            payload = b"".join(b"not json at all\n" for _ in range(n_bad))
            s.sendall(payload)

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # widen the lost-update window
    try:
        with src:
            threads = [threading.Thread(target=produce)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            deadline = time.time() + 10
            want = n_threads * n_bad
            while time.time() < deadline and src.parse_errors < want:
                time.sleep(0.02)
            assert src.parse_errors == want
            assert src.records_parsed == 0
    finally:
        sys.setswitchinterval(old_interval)


def test_tcp_source_close_joins_accept_thread():
    """ISSUE 13 resource-lifecycle regression: close() must join the
    accept thread (bounded) — before the fix the Thread object outlived
    close(), which the conftest leak fixture only caught when a test
    happened to observe the window."""
    src = TcpJsonlSource(["s0"], port=0).start()
    src.close()
    assert not src._thread.is_alive()


# ------------------------------------------- vector records (ISSUE 43) --
# a model of F > 1 fields (node_preset: cpu, mem, net of one node) takes
# {"id", "values": [..F..], "ts"}; `null` is that field's missing sample


def _vstate(ids=("a", "b", "c"), n_fields=3):
    latest = np.full((len(ids), n_fields), np.nan, np.float32)
    return NativeJsonlState(list(ids), latest), latest


@needs_native
def test_vector_line_lands_whole_with_null_in_its_field_only():
    st, latest = _vstate()
    c = st.new_conn()
    c.feed(b'{"id": "a", "values": [1.5, null, "3"], "ts": 7}\n')
    assert latest[0, 0] == np.float32(1.5) and latest[0, 2] == 3.0
    assert np.isnan(latest[0, 1]) and np.isnan(latest[1:]).all()
    assert list(st.counters) == [1, 0, 0] and st.ts_buf[0] == 7
    assert list(st.value_counters) == [2, 1]
    # a later record overwrites the row whole, field by field
    c.feed(b'{"ts":8,"values":[ true ,2e1,-4 ] , "id":"a"}\n')
    assert latest[0].tolist() == [1.0, 20.0, -4.0] and st.ts_buf[0] == 8
    assert list(st.value_counters) == [5, 1]
    c.close()


@needs_native
@pytest.mark.parametrize("line", [
    b'{"id": "b", "values": [1, 2], "ts": 5}',          # shorter than F
    b'{"id": "b", "values": [1, 2, 3, 4], "ts": 5}',    # longer
    b'{"id": "b", "values": [], "ts": 5}',
    b'{"id": "b", "value": 1.0, "ts": 5}',             # `value` where
    b'{"id": "b", "values": 1.0, "ts": 5}',            # `values` is due
    b'{"id": "b", "values": "1,2,3", "ts": 5}',
    b'{"id": "b", "values": {"cpu": 1}, "ts": 5}',
    b'{"id": "b", "values": [1, [2], 3], "ts": 5}',     # nested element
    b'{"id": "b", "values": [1, 2, 3,], "ts": 5}',      # trailing comma
    b'{"id": "b", "values": [1,, 3], "ts": 5}',
    b'{"id": "b", "values": [1, "x", 3], "ts": 5}',     # unconvertible
    b'{"id": "b", "values": [1, "null", 3], "ts": 5}',  # quoted null
    b'{"id": "b", "values": [1, 2, 0x3], "ts": 5}',
], ids=lambda b: b.decode()[11:40])
def test_vector_parse_error_writes_nothing(line):
    st, latest = _vstate()
    c = st.new_conn()
    c.feed(b'{"id": "b", "values": [7, 8, 9], "ts": 3}\n' + line + b"\n")
    assert latest[1].tolist() == [7.0, 8.0, 9.0]  # no field of it moved
    assert list(st.counters) == [1, 1, 0] and st.ts_buf[0] == 3
    assert list(st.value_counters) == [3, 0]
    c.close()


@needs_native
def test_vector_effect_order_is_the_scalar_paths():
    st, latest = _vstate()
    c = st.new_conn()
    # unknown id BEFORE value conversion: a bad list on an unknown id is
    # unknown, not a parse error
    c.feed(b'{"id": "ghost", "values": [1]}\n')
    assert list(st.counters) == [0, 0, 1]
    # values BEFORE ts: a bad ts keeps the row and its value counts, but
    # the record is a parse error, never a parsed success
    c.feed(b'{"id": "c", "values": [4, null, 6], "ts": "xx"}\n')
    assert latest[2, 0] == 4.0 and np.isnan(latest[2, 1]) \
        and latest[2, 2] == 6.0
    assert list(st.counters) == [0, 1, 1]
    assert list(st.value_counters) == [2, 1] and st.ts_buf[0] == 0
    c.close()


@needs_native
def test_scalar_table_ignores_values_and_counts_its_nulls():
    """At F = 1 `values` is an extra field like any other and the record is
    {"id", "value", "ts"}; the value counters move there too."""
    st, latest = _state()
    c = st.new_conn()
    c.feed(b'{"id": "a", "values": [1.0], "ts": 5}\n')  # no `value`
    assert list(st.counters) == [0, 1, 0] and np.isnan(latest).all()
    c.feed(b'{"id": "a", "value": 2.5, "values": [9, 9, 9]}\n'
           b'{"id": "a", "value": null}\n')
    assert list(st.counters) == [2, 1, 0] and np.isnan(latest[2])
    assert list(st.value_counters) == [1, 1]
    c.close()


@needs_native
def test_native_state_refuses_a_table_it_cannot_write():
    ids = ["a", "b"]
    for bad in (np.zeros((2, 65), np.float32), np.zeros((3, 2), np.float32),
                np.zeros((2, 2, 2), np.float32), np.zeros((2, 0), np.float32),
                np.zeros((2, 3), np.float64),
                np.zeros((3, 2), np.float32).T):
        with pytest.raises(ValueError):
            NativeJsonlState(ids, bad)
    with pytest.raises(ValueError):
        TcpJsonlSource(ids, n_fields=0)


def test_scalar_source_is_the_parents_shape():
    """F = 1 keeps the [n_streams] table (the parent's object): what
    `cluster-256-live` runs is the scalar path, not a [n, 1] one."""
    for native in (None, False):
        src = TcpJsonlSource(["a", "b"], native=native)
        try:
            assert src.n_fields == 1 and src._latest.shape == (2,)
            values, _ts = src(0)
            assert values.shape == (2,) and values.dtype == np.float32
            src.set_ids(["b", "c", "a"])
            assert src._latest.shape == (3,)
        finally:
            src._server.server_close()


def _fuzz_vector_records(seed: int, ids: list[str], n_fields: int,
                         n: int) -> list[bytes]:
    """Randomized vector lines: good rows, `null`s, wrong lengths, `value`
    and `values` mixed up or both present, nested and unconvertible
    elements, bad ts, unknown ids, shuffled keys, malformed tails."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        sid = ids[int(rng.integers(0, len(ids)))] \
            if rng.random() < 0.85 else "ghost"
        k = n_fields if rng.random() < 0.8 else int(rng.integers(0, 6))
        elems = [str(rng.choice([
            str(float(rng.normal(50, 20))), "null", "null",
            str(int(rng.integers(-100, 100))), '"7.5"', "true", "1e3",
            '"nope"', "[1]", '{"x": 1}', '"null"',
        ], p=[0.72, 0.08, 0.04, 0.04, 0.02, 0.02, 0.02, 0.02, 0.015, 0.01,
              0.015])) for _ in range(k)]
        sep_in = ", " if rng.random() < 0.7 else rng.choice([",", " , "])
        values = "[" + sep_in.join(elems) + "]"
        if rng.random() < 0.03:
            # (no trailing comma here: json.loads refuses the whole line,
            # the C scanner only the list — they differ on an unknown id,
            # a divergence from strict JSON the module's header documents)
            values = rng.choice(["3.5", '"1,2,3"', "null", "[[1, 2, 3]]"])
        ts = rng.choice([str(int(rng.integers(1, 10**9))), '"123"', '"9.5"',
                         "55.7", "null"], p=[0.8, 0.05, 0.05, 0.05, 0.05])
        fields = [f'"id": "{sid}"', f'"ts": {ts}',
                  '"extra": {"nested": [1, "x"]}']
        r = rng.random()
        if r < 0.88:
            fields.append(f'"values": {values}')
        elif r < 0.94:  # a scalar record where a vector one is due
            fields.append(f'"value": {float(rng.normal()):.3f}')
        else:  # both keys: `values` decides
            fields += [f'"values": {values}', '"value": 1.25']
        rng.shuffle(fields)
        line = "{" + (", " if rng.random() < 0.8 else ",").join(fields) + "}"
        if rng.random() < 0.05:
            line = line[: int(rng.integers(1, len(line)))]  # malformed tail
        out.append(line.encode())
    return out


@needs_native
@pytest.mark.parametrize("seed,n_fields", [(1, 3), (2, 3), (3, 5), (4, 2)])
def test_socket_parity_fuzz_vector_records(seed, n_fields):
    """(2a) The Python `_feed_lines` path is the plain parser: over a
    seeded fuzz of vector lines, split across `recv`s at awkward sizes,
    the C parser gives the same table, the same ts and the same counters
    — records, parse errors, unknown ids, values and nulls."""
    ids = [f"n{i}" for i in range(6)]
    lines = _fuzz_vector_records(seed, ids, n_fields, 500)
    payload = b"\n".join(lines) + b"\n"
    sentinel = json.dumps({"id": ids[0], "values": [31337.0] * n_fields}
                          ).encode() + b"\n"
    results = []
    for native in (True, False):
        src = TcpJsonlSource(ids, native=native, n_fields=n_fields)
        with src:
            assert src.native_active == native
            assert src._latest.shape == (len(ids), n_fields)
            with socket.create_connection(src.address, timeout=5.0) as s:
                data = payload + sentinel
                for off in range(0, len(data), 251):  # lines split mid-record
                    s.sendall(data[off:off + 251])
            deadline = time.time() + 20
            while time.time() < deadline:
                with src._lock:
                    if (src._latest[0] == np.float32(31337.0)).all():
                        break
                time.sleep(0.01)
            values, ts = src(0)
            drained, _ = src(1)
        assert values.shape == (len(ids), n_fields)
        assert np.isnan(drained).all()  # snapshot AND drain, every field
        results.append((values, ts, src.parse_errors, src.unknown_ids,
                        src.records_parsed, src.values_parsed,
                        src.values_null))
    (v_n, *tally_n), (v_p, *tally_p) = results
    assert np.array_equal(v_n, v_p, equal_nan=True)
    assert tally_n == tally_p
    _ts, pe, unk, rec, vals, nulls = tally_n
    assert pe > 0 and unk > 0 and rec > 0 and nulls > 0
    # a sound record writes F values, nulls among them; a record with a bad
    # ts wrote its values too, so the count is at least the records'
    assert vals + nulls >= rec * n_fields


@needs_native
@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_vector_set_ids_carries_rows_by_id(native):
    """set_ids on the [n, F] table: retained nodes keep this slot's row BY
    ID, new ones start all-missing, on both parse paths — and a partial
    line held by a connection completes against the new table."""
    src = TcpJsonlSource(["a", "b"], native=native, n_fields=3,
                         track_unknown=True).start()
    try:
        with socket.create_connection(src.address, timeout=5.0) as s:
            s.sendall(b'{"id": "a", "values": [7, null, 9]}\n'
                      b'{"id": "c", "values": [1, 2')
            deadline = time.time() + 5
            while time.time() < deadline and src.records_parsed < 1:
                time.sleep(0.01)
            src.set_ids(["c", "a"])
            assert src._latest.shape == (2, 3)
            s.sendall(b", 3]}\n")
        deadline = time.time() + 5
        while time.time() < deadline and src.records_parsed < 2:
            time.sleep(0.01)
        assert src.drain_unknown() == []  # c was registered before its line
        values, _ = src(0)
        assert values[0].tolist() == [1.0, 2.0, 3.0]
        assert values[1][0] == 7.0 and np.isnan(values[1][1]) \
            and values[1][2] == 9.0
    finally:
        src.close()


def test_send_jsonl_sends_vector_records_with_null_for_a_missing_metric():
    with TcpJsonlSource(["a"], native=False, n_fields=3) as src:
        row = np.array([1.5, np.nan, 3.0], np.float32)
        assert send_jsonl(src.address, [{"id": "a", "values": row,
                                         "ts": 9}]) == 1
        deadline = time.time() + 5
        while time.time() < deadline and src.records_parsed < 1:
            time.sleep(0.01)
        values, ts = src(0)
    assert ts == 9 and np.array_equal(values[0], row, equal_nan=True)
    assert (src.values_parsed, src.values_null) == (2, 1)  # NaN went as null
