"""Live metric sources for the service loop (SURVEY.md C18, L4).

The reference's metrics collector polls per-node stats endpoints at a fixed
cadence and normalizes them into (node, metric, t, value) tuples (SURVEY.md
§2.2 C18, §3.3). These adapters are that collector for the TPU service loop:
each is a callable matching `live_loop`'s source contract —
``source(tick) -> (values [G] f32, ts unix-sec)`` — batching one value per
registered stream id per tick, with NaN for streams the poll did not return
(the encoder's missing-sample path scores them without corrupting state).

Two transports:

- :class:`HttpPollSource` — pull. Polls one endpoint returning JSON
  ``{"ts": <unix>, "metrics": {"<stream_id>": <value>, ...}}`` (the
  Prometheus-exporter-style shape the reference scrapes).
- :class:`TcpJsonlSource` — push. A background listener accepts JSONL
  records ``{"id": ..., "value": ..., "ts": ...}`` from any number of
  producers; each tick drains the latest value per stream. For a model
  of F > 1 fields (``n_fields``: one model a node over its cpu/mem/net)
  a record is ``{"id": ..., "values": [v0, .., vF-1], "ts": ...}``, a
  metric the collector missed is ``null``, and a tick drains ``[G, F]``.
"""

from __future__ import annotations

import json
import socket
import socketserver
import threading
import time
import urllib.request

import numpy as np

from rtap_tpu.obs import get_registry
from rtap_tpu.obs.trace import span

__all__ = ["HttpPollSource", "TcpJsonlSource", "BinaryBatchSource",
           "send_jsonl"]


def __getattr__(name):
    # The production wire-speed source lives in rtap_tpu.ingest
    # (ISSUE 7) but belongs to this module's source family — re-export
    # lazily so importing the JSONL sources never pays the ingest
    # package's import.
    if name == "BinaryBatchSource":
        from rtap_tpu.ingest.server import BinaryBatchSource

        return BinaryBatchSource
    raise AttributeError(name)


class HttpPollSource:
    """Poll an HTTP metrics endpoint once per tick.

    Stream ids absent from a poll (or a failed poll) yield NaN for that tick:
    a live service must keep scoring the healthy streams when one exporter
    times out, not stall the whole group (the reference's collector has the
    same per-poll timeout shape).

    Failed polls get bounded in-tick retry (`retry`, transport errors
    only) and a per-endpoint circuit breaker (`breaker`): after
    `fail_threshold` consecutive failed polls the endpoint is skipped
    outright — NaN tick, zero network wait — until the cooldown passes,
    then one half-open probe decides. Without the breaker a dead
    exporter's connect timeout would eat a fixed slice of EVERY tick's
    cadence budget for the whole outage. Short-circuited polls count in
    `polls_short_circuited` (and the breaker's own registry metrics), not
    in `poll_failures` — no network attempt was made.

    `track_unknown=True` (serve --auto-register over HTTP): metric KEYS in
    the poll payload that are not registered stream ids are remembered as
    discovery candidates — the reference's collector discovers a node's
    metrics from what the exporter reports, exactly this shape. Bounded
    like the TCP listener's capture (an exporter spraying keys must not
    grow host memory).
    """

    #: same bound as TcpJsonlSource.MAX_UNKNOWN_TRACKED
    MAX_UNKNOWN_TRACKED = 4096

    def __init__(self, url: str, stream_ids: list[str], timeout_s: float = 0.5,
                 track_unknown: bool = False, retry=None, breaker=None):
        from rtap_tpu.resilience.policies import CircuitBreaker, Retry

        self.url = url
        self.stream_ids = list(stream_ids)
        self._known = set(self.stream_ids)
        self.timeout_s = timeout_s
        self.poll_failures = 0
        self.polls_short_circuited = 0
        # retry covers transient transport blips inside one tick; delays
        # stay well under the 1 s cadence budget (2 tries, <= ~0.06 s of
        # backoff). Parse errors are NOT retried — a malformed payload is
        # the exporter's steady state, not a blip.
        self._retry = retry if retry is not None else Retry(
            attempts=2, base_delay_s=0.05, max_delay_s=0.25, op="http_poll")
        self._breaker = breaker if breaker is not None else CircuitBreaker(
            fail_threshold=5, cooldown_s=30.0, name="http_poll")
        self._track_unknown = bool(track_unknown)
        self._unknown_seen: set[str] = set()
        self._obs_poll_failures = get_registry().counter(
            "rtap_obs_source_poll_failures_total",
            "HTTP metric polls that failed or timed out (whole-vector NaN "
            "ticks)")

    def _fetch(self) -> dict:
        with urllib.request.urlopen(self.url, timeout=self.timeout_s) as r:
            return json.loads(r.read().decode())

    def __call__(self, tick: int) -> tuple[np.ndarray, int]:
        values = np.full(len(self.stream_ids), np.nan, np.float32)
        ts = int(time.time())
        if not self._breaker.allow():
            # open breaker: the endpoint is known-dead; report missing
            # samples immediately instead of paying the connect timeout
            self.polls_short_circuited += 1
            return values, ts
        try:
            payload = self._retry.call(self._fetch, retry_on=(OSError,))
            metrics = payload.get("metrics", {})
            ts = int(payload.get("ts", ts))
            for i, sid in enumerate(self.stream_ids):
                v = metrics.get(sid)
                if v is None:
                    continue
                try:
                    values[i] = np.float32(v)
                except (TypeError, ValueError):  # rtap: allow[except-silent]
                    # one unconvertible metric (a version string, say) is
                    # THAT stream's missing sample, not a poll failure —
                    # the rest of the vector must still fill
                    pass
            if self._track_unknown and isinstance(metrics, dict):
                for key, v in metrics.items():
                    if not isinstance(key, str) or key in self._known:
                        continue
                    # discovery candidates must carry a usable numeric
                    # value: a string/null metric would claim a pad slot
                    # for a stream that can never score (and previously
                    # poison later polls)
                    try:
                        float(v)
                    except (TypeError, ValueError):
                        continue
                    if len(self._unknown_seen) < self.MAX_UNKNOWN_TRACKED:
                        self._unknown_seen.add(key)
            self._breaker.record_success()
        except Exception:
            self.poll_failures += 1
            self._obs_poll_failures.inc()
            self._breaker.record_failure()
        return values, ts

    # ---- dynamic membership (serve --auto-register) ----
    def drain_unknown(self) -> list[str]:
        """Pop unregistered metric keys seen in polls since the last drain
        (sorted for deterministic registration order)."""
        seen = sorted(self._unknown_seen)
        self._unknown_seen.clear()
        return seen

    def set_ids(self, stream_ids: list[str]) -> None:
        """Adopt the registry's (possibly grown/shrunk) dispatch order.
        Polling is stateless per tick — no value carry-over needed; the
        next poll simply fills the new vector by id."""
        self.stream_ids = list(stream_ids)
        self._known = set(self.stream_ids)


def _field_f32(v) -> np.float32:
    """One element of a vector record's ``values`` list: ``null`` is that
    field's missing sample, a nested list or object no value at all
    (np.float32 would make an array of it)."""
    if v is None:
        return np.float32(np.nan)
    if isinstance(v, (list, dict)):
        raise TypeError("a field's value is a number, not a container")
    return np.float32(v)


class TcpJsonlSource:
    """Push transport: listens on a TCP port for newline-delimited JSON
    records and keeps the latest value per stream; each tick snapshots them.

    `n_fields` is the model's (``ModelConfig.n_fields``), never taken from
    a record. At 1 the table is ``[n_streams]`` and a record carries one
    ``"value"``. At F > 1 the table is ``[n_streams, F]`` and a record
    carries ``"values"``: a list of exactly F elements, ``null`` for a
    missing metric (NaN in that field only); the row is written whole, so
    a node's F values are scored in one tick and never split over two. A
    list of another length, a ``"value"`` where ``"values"`` is due (or
    the reverse) is a parse error and writes nothing. Both parsers keep
    one effect order: unknown id before value conversion, values before
    ``ts``, success counted last.

    Start/stop with a context manager (or .start()/.close()). The listener
    thread is a daemon; record parse errors are counted, never raised (a
    malformed producer must not kill the scoring loop).
    """

    #: bound on remembered unknown-id NAMES (track_unknown mode): a
    #: misbehaving producer spraying random ids must not grow host memory
    MAX_UNKNOWN_TRACKED = 4096

    def __init__(self, stream_ids: list[str], host: str = "127.0.0.1", port: int = 0,
                 native: bool | None = None, track_unknown: bool = False,
                 n_fields: int = 1):
        if n_fields < 1:
            raise ValueError(f"n_fields must be >= 1, got {n_fields}")
        self.stream_ids = list(stream_ids)
        self.n_fields = int(n_fields)
        self._index = {sid: i for i, sid in enumerate(self.stream_ids)}
        self._latest = self._empty_table(len(self.stream_ids))
        self._latest_ts = 0
        self._lock = threading.Lock()
        self._py_parse_errors = 0
        self._py_unknown_ids = 0
        # values written non-null / values that came as ``null`` on the
        # wire, as the C parser's value_counters count them
        self._py_values = 0
        self._py_values_null = 0
        self._py_records = 0  # successes on the Python fallback path —
        # counted like the C parser's COUNTER_PARSED so records_parsed
        # (and rtap_obs_ingest_records_total) agree across parser
        # backends (ISSUE 7 satellite; pre-fix the Python path returned
        # None and the counter only moved natively)
        # track_unknown: remember the NAMES of unknown ids so serve
        # --auto-register can lazily create models for them (SURVEY.md
        # C19). Both parse paths capture names: the C parser appends them
        # to a bounded buffer drained each tick, the Python handler adds
        # them to the bounded set below.
        self._track_unknown = bool(track_unknown)
        self._unknown_seen: set[str] = set()
        # ingest health mirrored into the telemetry registry once per tick
        # (the delta sync in __call__): the parse tallies live in C/handler
        # state for per-record cheapness; _obs_synced remembers how much of
        # this instance's tally already landed in the global counters
        obs = get_registry()
        self._obs_synced = {"parse_errors": 0, "unknown_ids": 0,
                            "records_parsed": 0, "values_parsed": 0,
                            "values_null": 0}
        self._obs_parse_errors = obs.counter(
            "rtap_obs_ingest_parse_errors_total",
            "malformed JSONL records dropped by the TCP listener")
        self._obs_unknown_ids = obs.counter(
            "rtap_obs_ingest_unknown_ids_total",
            "records for unregistered stream ids (claim candidates under "
            "--auto-register, otherwise dropped)")
        self._obs_records = obs.counter(
            "rtap_obs_ingest_records_total",
            "successfully parsed ingest records (JSONL records and "
            "binary batch rows, both parser backends)")
        self._obs_values = obs.counter(
            "rtap_obs_ingest_values_total",
            "metric values the TCP listener wrote into its table (one a "
            "scalar record, up to F a vector record; nulls apart)")
        self._obs_values_null = obs.counter(
            "rtap_obs_ingest_values_null_total",
            "values that arrived as null on the wire: a metric the "
            "collector missed, scored as that field's missing sample")
        # Native C parse path (rtap_tpu/native/jsonl_parser.c): the whole
        # recv-chunk drain in one locked C call instead of per-record
        # json.loads + dict lookup + lock — the host core feeding 100k
        # streams cannot afford microseconds per record. native=None
        # auto-detects (falls back to Python if the toolchain/build is
        # unavailable); True requires it; False forces pure Python.
        self._nstate = None
        if native is not False:
            try:
                from rtap_tpu.native import NativeJsonlState

                self._nstate = NativeJsonlState(
                    self.stream_ids, self._latest,
                    track_unknown=self._track_unknown)
            except Exception:
                if native:
                    raise
        outer = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                # one `rtap.ingest.feed` span per recv batch, on either
                # parse path (obs/trace.py:SPANS): the layer's busy time
                # and its wait for the lock (`wait_us`), beside the count
                # it has (records_parsed)
                if outer._nstate is not None:
                    conn = outer._nstate.new_conn()
                    try:
                        while True:
                            data = self.connection.recv(65536)
                            if not data:
                                break
                            sp = span("rtap.ingest.feed",
                                      bytes=len(data)).begin()
                            with outer._lock:
                                locked = time.perf_counter()
                                v0, z0 = outer._nstate.value_counters
                                conn.feed(data)
                                v1, z1 = outer._nstate.value_counters
                            sp.end(wait_us=int((locked - sp.t0) * 1e6),
                                   values=int(v1 - v0), nulls=int(z1 - z0))
                        with outer._lock:
                            conn.flush()  # unterminated final line, like rfile
                    finally:
                        conn.close()
                    return
                # the Python fallback: lines split on "\n" as rfile's are,
                # the unterminated final line fed at EOF
                tail = b""
                while True:
                    data = self.connection.recv(65536)
                    batch = (tail + data).split(b"\n")
                    tail = batch.pop()
                    if not data and tail:
                        batch, tail = [tail], b""
                    if batch:
                        sp = span("rtap.ingest.feed", bytes=len(data)).begin()
                        waited, values, nulls = outer._feed_lines(batch)
                        sp.end(wait_us=int(waited * 1e6), values=values,
                               nulls=nulls)
                    if not data:
                        break

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.address = self._server.server_address  # (host, bound port)
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="rtap-sources-accept",
                                        daemon=True)

    def _empty_table(self, n: int) -> np.ndarray:
        """The latest-value table of `n` streams, all missing: ``[n]`` at
        one field (the scalar path's own object), ``[n, n_fields]`` else."""
        shape = (n,) if self.n_fields == 1 else (n, self.n_fields)
        return np.full(shape, np.nan, np.float32)

    def _feed_lines(self, lines: list[bytes]) -> tuple[float, int, int]:
        """The Python parse path over one batch of complete lines -> (the
        seconds it waited for the lock, values written non-null, null)."""
        waited = 0.0
        F = self.n_fields
        wrote = wrote_null = 0  # this batch's share of the two tallies
        for line in lines:
            try:
                rec = json.loads(line)
                sid = rec["id"]
                # index resolved under the SAME lock as the write:
                # set_ids swaps (_index, _latest) together, and an
                # index from the old mapping must never address the
                # new array (it would misroute the sample). Effect
                # ORDER is pinned by the native-parity fuzz: the
                # unknown check precedes value conversion (bad value
                # on an unknown id = unknown, not parse error), and
                # the value write precedes ts conversion (bad ts
                # counts a parse error but KEEPS the value) — the C
                # parser implements the same order.
                t_ask = time.perf_counter()
                with self._lock:
                    waited += time.perf_counter() - t_ask
                    i = self._index.get(sid)
                    if i is None:
                        self._py_unknown_ids += 1
                        if self._track_unknown and \
                                isinstance(sid, str) and \
                                len(self._unknown_seen) < \
                                self.MAX_UNKNOWN_TRACKED:
                            self._unknown_seen.add(sid)
                        continue
                    if F == 1:
                        v = rec["value"]
                        self._latest[i] = np.float32(v)
                        nulls = int(v is None)
                    else:
                        # the whole row converts before any of it is
                        # written: a short, long or unconvertible list
                        # writes nothing
                        vals = rec["values"]
                        if not isinstance(vals, list) or len(vals) != F:
                            raise ValueError(
                                f"'values' must be a list of {F}")
                        self._latest[i] = [_field_f32(v) for v in vals]
                        nulls = sum(v is None for v in vals)
                    self._py_values += F - nulls
                    self._py_values_null += nulls
                    wrote += F - nulls
                    wrote_null += nulls
                    self._latest_ts = max(self._latest_ts,
                                          int(rec.get("ts", 0)))
                    # success is counted AFTER the ts conversion:
                    # a bad ts keeps the value but counts as a
                    # parse error, not a parsed record — the
                    # order the C parser implements (pinned by
                    # the native-parity fuzz)
                    self._py_records += 1
            except Exception:
                # under the lock like every other tally: handler
                # threads are one-per-connection, and an
                # unguarded += across N malformed producers
                # loses increments (read-modify-write race the
                # analyzer's race pass flags)
                with self._lock:
                    self._py_parse_errors += 1
        return waited, wrote, wrote_null

    def start(self) -> "TcpJsonlSource":
        self._thread.start()
        return self

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    def __enter__(self) -> "TcpJsonlSource":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def parse_errors(self) -> int:
        n = int(self._nstate.counters[1]) if self._nstate is not None else 0
        return self._py_parse_errors + n

    @property
    def unknown_ids(self) -> int:
        n = int(self._nstate.counters[2]) if self._nstate is not None else 0
        return self._py_unknown_ids + n

    @property
    def records_parsed(self) -> int:
        """Successful-record count — both parser backends (a record
        counts once its value AND ts converted, the C parser's rule)."""
        n = int(self._nstate.counters[0]) if self._nstate is not None else 0
        return self._py_records + n

    @property
    def values_parsed(self) -> int:
        """Values written into the table, nulls apart (one a scalar record,
        F less its nulls a vector record) — both parser backends. Counted
        at the write, which precedes the ``ts`` conversion: a record whose
        ``ts`` is bad keeps its values and counts them."""
        n = int(self._nstate.value_counters[0]) \
            if self._nstate is not None else 0
        return self._py_values + n

    @property
    def values_null(self) -> int:
        """Values that arrived as ``null``: a metric the collector missed,
        that field's missing sample."""
        n = int(self._nstate.value_counters[1]) \
            if self._nstate is not None else 0
        return self._py_values_null + n

    @property
    def native_active(self) -> bool:
        return self._nstate is not None

    # ---- dynamic membership (serve --auto-register) ----
    def drain_unknown(self) -> list[str]:
        """Pop the unknown-id names seen since the last drain (sorted for
        deterministic registration order). Empty unless track_unknown."""
        if not self._track_unknown:
            return []
        with self._lock:
            if self._nstate is not None:
                for sid in self._nstate.drain_unknown_names():
                    if len(self._unknown_seen) < self.MAX_UNKNOWN_TRACKED:
                        self._unknown_seen.add(sid)
            seen = sorted(self._unknown_seen)
            self._unknown_seen.clear()
        return seen

    def set_ids(self, stream_ids: list[str]) -> None:
        """Replace the accepted id set (registry membership changed).

        Latest values carry over BY ID — a retained stream must not lose
        the sample that arrived this tick — and new ids start at NaN. The
        snapshot order is the caller's (= the registry's dispatch order:
        live_loop routes values positionally). Works on both parse paths:
        the native table swaps under the same lock that serializes
        feed(), so per-connection parsers keep their partial-line state
        and observe the new table on their next line."""
        with self._lock:
            latest = self._empty_table(len(stream_ids))  # field axis carried
            for j, sid in enumerate(stream_ids):
                i = self._index.get(sid)
                if i is not None:
                    latest[j] = self._latest[i]
            if self._nstate is not None:
                self._nstate.set_table(stream_ids, latest)
            self.stream_ids = list(stream_ids)
            self._index = {sid: i for i, sid in enumerate(self.stream_ids)}
            self._latest = latest

    def __call__(self, tick: int) -> tuple[np.ndarray, int]:
        """-> (values ``[G]``, or ``[G, n_fields]`` for vector records, ts).
        Snapshot AND DRAIN: values reset to NaN after each tick, so a
        producer that stops pushing yields missing samples (NaN) rather than
        its stale last value being re-scored forever — a silent outage must
        surface as missing data, not as a suspiciously flat healthy metric."""
        # `rtap.ingest.snapshot` ends at the snapshot instant as the program
        # itself knows it (the loop's `source` span also holds whatever
        # wraps this call)
        sp = span("rtap.ingest.snapshot", tick=tick).begin()
        with self._lock:
            locked = time.perf_counter()
            values = self._latest.copy()
            self._latest[:] = np.nan
            if self._nstate is not None:
                self._latest_ts = max(self._latest_ts, int(self._nstate.ts_buf[0]))
            ts = self._latest_ts or int(time.time())
        sp.end(wait_us=int((locked - sp.t0) * 1e6), fields=self.n_fields)
        # once-per-tick delta sync of THIS instance's ingest tallies into
        # the process-global registry counters (outside the lock: reads +
        # obs-cell increments only). Per-instance deltas, never a raise-
        # to-total sync against the global counter's current value: the
        # registry counter outlives any one source, so two sources over a
        # process lifetime (reconnect, tests) must SUM, and a replacement
        # source's from-zero tally must not be masked by its predecessor's.
        # Each tally is read ONCE into a local — the handler thread keeps
        # bumping it, and an inc/store pair reading twice would drop any
        # increments landing between the reads.
        for tally, counter in (("parse_errors", self._obs_parse_errors),
                               ("unknown_ids", self._obs_unknown_ids),
                               ("records_parsed", self._obs_records),
                               ("values_parsed", self._obs_values),
                               ("values_null", self._obs_values_null)):
            n = getattr(self, tally)
            counter.inc(max(0, n - self._obs_synced[tally]))
            self._obs_synced[tally] = n
        return values, ts


#: records per sendall — bounds what one mid-stream connection drop can
#: leave in doubt (the failing batch is retried; earlier batches are known
#: delivered)
_SEND_BATCH = 512


def _wire_record(record: dict) -> dict:
    """A record as it goes on the wire: a vector record's ``values`` may be
    any sequence of numbers (a numpy row); a missing metric — ``None`` or
    NaN — is sent as ``null``. A scalar record goes as it is."""
    if "values" not in record:
        return record
    values = [v.item() if isinstance(v, np.generic) else v
              for v in record["values"]]
    return {**record, "values": [
        None if isinstance(v, float) and v != v else v for v in values]}


def send_jsonl(address: tuple[str, int], records: list[dict],
               retry=None) -> int:
    """Producer-side helper (tests, demos, soak feeders): push records to
    a :class:`TcpJsonlSource` listener — scalar ``{"id", "value", "ts"}``
    or vector ``{"id", "values": [..], "ts"}`` ones (:func:`_wire_record`).
    Returns the count actually handed to the kernel.

    A listener restart mid-soak used to surface here as a raised
    ``ConnectionRefusedError`` that killed the producer; now the
    connection is retried with bounded exponential backoff (`retry`;
    default 4 attempts, <= ~1 s of total backoff) and the return value
    says how many records were delivered — the caller decides whether a
    shortfall is fatal. Delivery is at-least-once across retries: the
    batch in flight when a connection dropped is resent whole, which is
    harmless against TcpJsonlSource's latest-value-per-stream semantics.
    """
    from rtap_tpu.resilience.policies import Retry

    if retry is None:
        retry = Retry(attempts=4, base_delay_s=0.05, max_delay_s=0.5,
                      op="send_jsonl")
    payloads = [
        "".join(json.dumps(_wire_record(r)) + "\n"
                for r in records[i:i + _SEND_BATCH]).encode()
        for i in range(0, len(records), _SEND_BATCH)
    ]
    sizes = [min(_SEND_BATCH, len(records) - i)
             for i in range(0, len(records), _SEND_BATCH)]
    delivered = 0
    next_batch = 0
    for attempt in range(1, retry.attempts + 1):
        try:
            with socket.create_connection(address, timeout=2.0) as s:
                while next_batch < len(payloads):
                    s.sendall(payloads[next_batch])
                    delivered += sizes[next_batch]
                    next_batch += 1
            return delivered
        except OSError:
            if attempt == retry.attempts:
                return delivered
            retry.backoff(attempt)
    return delivered
