"""Alert emission + throughput accounting (host side).

The reference thresholds anomaly log-likelihood and pushes alerts to a
dashboard (SURVEY.md C20/C22, §3.3). v1 keeps the design but emits JSONL —
one object per alert — plus periodic throughput stats implementing the
north-star counter "anomaly-scored metrics/sec/chip" (SURVEY.md §5
"Metrics / logging").
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import IO

import numpy as np

from rtap_tpu.obs import get_registry


def format_alert_line(alert_id, stream: str, ts: int, value,
                      raw_score: float, log_likelihood: float,
                      top_fields=None) -> str:
    """THE alert-line serialization — one function so every producer of
    alert JSONL bytes (AlertWriter.emit_batch and the hot-standby
    follower's buffered splice, resilience/replicate.py) emits
    byte-identical lines for identical inputs: the failover soak's
    per-id record-equality check depends on it. ``value`` may be a
    scalar or a 1-D multivariate row."""
    val = np.asarray(value)
    return json.dumps(
        {
            **({"alert_id": alert_id} if alert_id is not None else {}),
            "stream": stream,
            "ts": int(ts),
            "value": float(val) if val.ndim == 0
            else [float(x) for x in val],
            "raw_score": float(raw_score),
            "log_likelihood": float(log_likelihood),
            **({"top_fields": top_fields} if top_fields is not None else {}),
        }
    ) + "\n"


def heal_torn_tail(path: str) -> int:
    """Append a newline if `path` ends mid-line (a writer killed
    mid-``write``): the fragment becomes its own unparseable — and
    therefore skipped — line instead of merging with the next append
    and corrupting BOTH records. Shared by the alert sink on reopen and
    the supervisor's incident-stream appends. Returns bytes added
    (0 or 1); a missing/empty/unwritable path heals nothing."""
    try:
        with open(path, "rb") as f:
            f.seek(-1, 2)
            if f.read(1) == b"\n":
                return 0
    except (OSError, ValueError):
        return 0
    try:
        with open(path, "a") as f:
            f.write("\n")
    except OSError:
        return 0
    return 1


class AlertWriter:
    """JSONL alert sink. One line per (stream, tick) whose score crosses the
    threshold; `None` path writes nowhere but still counts. Structured
    watchdog events (`emit_event`) share the stream, discriminated by their
    "event" key — one file tells the whole incident story in order.

    The sink is NON-FATAL: a full disk must never kill scoring. Every
    write goes through retry-then-quarantine — one immediate retry on
    ``OSError``, then a circuit breaker (`breaker`; 3 consecutive failed
    batches open it) quarantines the sink: lines are counted and DROPPED
    (``dropped``, ``rtap_obs_alert_lines_dropped_total``) with zero write
    attempts until the cooldown admits a probe batch. A probe that lands
    re-closes the breaker and the stream resumes — with a gap, which the
    drop counters size. ``count`` tracks threshold crossings regardless
    of sink health (it feeds the loop stats, not the file).

    `flush_every=N` flushes once per N batches instead of per batch —
    the fsync-adjacent cost dominated emit at high alert rates. The
    default 1 keeps flush-per-batch crash-safety: a killed serve loses at
    most the current batch. Events always flush (rare, load-bearing).

    Durability (ISSUE 5, docs/RESILIENCE.md): every alert line carries a
    stable ``alert_id`` (``group:stream:tick`` — the group index, the
    stream id, and the GROUP's own tick counter, identical across
    restarts) whenever the caller supplies ``group``/``tick``. The
    writer tracks its byte offset into the sink (``sink_offset``; the
    checkpoint meta records it at drained save instants as the alert
    cursor) and can be armed with a resume suppression set
    (``arm_suppression``): alert ids already on disk from a crashed
    run's post-checkpoint window are counted and NOT re-written during
    journal replay — exactly-once across the crash. Opening an existing
    sink whose last line was torn mid-write (killed mid-``writelines``)
    first heals it with a newline so subsequent lines stay parseable.
    """

    def __init__(self, path: str | None = None, flush_every: int = 1,
                 breaker=None, attributor=None, fence=None,
                 correlator=None, latency=None):
        import os

        from rtap_tpu.resilience.policies import CircuitBreaker

        if flush_every < 1:
            raise ValueError(f"flush_every must be >= 1; got {flush_every}")
        self.path = path
        # leader fencing (ISSUE 8, resilience/replicate.py): a callable
        # consulted before every sink write; False means this process no
        # longer holds the leadership lease — a paused old leader that
        # wakes up after a standby promoted must NOT append to the alert
        # sink (the new leader owns the stream now). Fenced lines are
        # dropped + counted, never written; the loop itself also exits
        # on fence loss, this is the last-line guard under it.
        self._fence = fence
        self.fenced_drops = 0
        # per-alert provenance (service/attribution.py, serve
        # --alert-attribution): alert lines gain a top_fields block.
        # History advances on EVERY batch (attribution compares against
        # the previous tick), alert or not.
        self._attributor = attributor
        # topology-aware incident correlation (ISSUE 9,
        # rtap_tpu/correlate/): every NON-SUPPRESSED alert batch this
        # writer lands on the sink also folds into the correlator's
        # windows (suppressed ids were delivered by the crashed run —
        # the correlator's resume scan of the sink tail already saw
        # them, and dropped batches never fold, so the fold mirrors the
        # DISK exactly once by construction).
        self._correlator = correlator
        # detection-latency observability (ISSUE 11, obs/latency.py):
        # every batch that reached the sink observes wall-clock-minus-
        # source-ts per alert into the e2e detect sketch — the sink
        # write IS the delivery moment the paper's real-time claim is
        # judged by. Pure observation: bytes on the stream are identical
        # with the tracker armed or absent.
        self._latency = latency
        self._offset = 0  # bytes handed to the sink (the alert cursor)
        self.torn_heals = 0
        if path:
            try:
                self._offset = os.path.getsize(path)
            except OSError:
                self._offset = 0
            # heal a torn tail from a killed writer: without the newline
            # the next append would merge into the partial line and
            # corrupt BOTH records for line consumers
            self.torn_heals = heal_torn_tail(path)
            self._offset += self.torn_heals
        self._fh: IO[str] | None = open(path, "a") if path else None
        self.count = 0
        self.written = 0  # alert lines handed to the sink
        self.suppressed = 0  # resume-suppressed (already-delivered) lines
        self._suppress: set[str] = set()
        self.dropped = 0
        self.sink_quarantines = 0  # times the breaker opened on the sink
        self.flush_every = int(flush_every)
        self._batches_since_flush = 0
        self._breaker = breaker if breaker is not None else CircuitBreaker(
            fail_threshold=3, cooldown_s=5.0, name="alert_sink")
        obs = get_registry()
        self._obs_alerts = obs.counter(
            "rtap_obs_alerts_total", "alert lines emitted (threshold "
            "crossings that survived debounce)")
        self._obs_events = obs.counter(
            "rtap_obs_alert_stream_events_total",
            "structured watchdog/ops events written to the alert stream")
        self._obs_emit = obs.histogram(
            "rtap_obs_alert_emit_seconds",
            "wall seconds per emit_batch call (JSONL format + write + flush)")
        self._obs_sink_errors = obs.counter(
            "rtap_obs_alert_sink_errors_total",
            "OSError write/flush failures against the alert sink (each "
            "failed batch counts once, after its immediate retry)")
        self._obs_dropped = obs.counter(
            "rtap_obs_alert_lines_dropped_total",
            "alert/event lines dropped while the sink was failing or "
            "quarantined (full disk etc. — scoring continued)")
        self._obs_suppressed = obs.counter(
            "rtap_obs_alerts_suppressed_total",
            "already-delivered alert ids suppressed during journal/"
            "checkpoint resume (exactly-once across a crash)")
        self._obs_quarantined = {
            kind: obs.counter(
                "rtap_obs_resilience_events_total",
                "structured resilience events by kind", event=kind)
            for kind in ("alert_sink_quarantined", "alert_sink_restored")
        }
        self._obs_fenced = obs.counter(
            "rtap_obs_alert_lines_fenced_total",
            "alert/event lines refused because this process lost the "
            "leadership lease (a fenced old leader must never append to "
            "the sink a promoted standby now owns)")

    def wrap_sink(self, wrap) -> None:
        """Wrap the underlying file object (the chaos engine's injection
        seam: faults land UNDER the retry/quarantine path, proving it)."""
        if self._fh is not None:
            self._fh = wrap(self._fh)

    def _safe_write(self, lines: list[str], force_flush: bool = False) -> bool:
        """Write + maybe flush, retry once, quarantine via the breaker.
        Never raises; failed/skipped lines are counted in ``dropped``.
        Returns True iff the lines were handed to the sink (the batch is
        all-or-nothing: one writelines call) — consumers that must stay
        consistent with the on-disk stream (the incident correlator's
        fold) key on it."""
        if self._fh is None or not lines:
            return False
        if self._fence is not None and not self._fence():
            self.fenced_drops += len(lines)
            self._obs_fenced.inc(len(lines))
            return False
        if not self._breaker.allow():
            self.dropped += len(lines)
            self._obs_dropped.inc(len(lines))
            return False
        was_closed = self._breaker.state == self._breaker.CLOSED
        wrote = False  # a flush-only failure must not re-write the lines
        # on retry (duplicated alert lines would corrupt bit-exactness
        # consumers of the stream)
        for attempt in (1, 2):  # retry once, immediately: transient EINTR/
            # EAGAIN-class blips recover; a full disk fails twice and
            # feeds the breaker
            try:
                if not wrote:
                    self._fh.writelines(lines)
                    wrote = True
                    # the alert cursor: bytes handed to the sink (exact
                    # disk offset whenever the buffer is flushed — the
                    # checkpoint path flushes before reading it)
                    self._offset += sum(len(ln.encode("utf-8", "replace"))
                                        for ln in lines)
                    self._batches_since_flush += 1
                if force_flush or self._batches_since_flush >= self.flush_every:
                    self._fh.flush()
                    self._batches_since_flush = 0
                self._breaker.record_success()
                if not was_closed:
                    # the probe landed: the sink is back. Say so ON the
                    # now-working stream, with the gap size.
                    self._obs_quarantined["alert_sink_restored"].inc()
                    self.emit_event({"event": "alert_sink_restored",
                                     "lines_dropped": self.dropped})
                return True
            except OSError:
                if attempt == 2:
                    self._obs_sink_errors.inc()
                    if not wrote:
                        # flush-only failures leave the lines in the
                        # stdio buffer — they land on a later successful
                        # flush, so counting them dropped would overstate
                        # the gap the restored event reports
                        self.dropped += len(lines)
                        self._obs_dropped.inc(len(lines))
                    self._breaker.record_failure()
                    if self._breaker.state == self._breaker.OPEN:
                        # quarantined: counted, not written (the sink is
                        # the thing that just died)
                        self.sink_quarantines += 1
                        self._obs_quarantined["alert_sink_quarantined"].inc()
        # both attempts raised: the lines reached the sink only if the
        # write itself landed and the failure was flush-only
        return wrote

    def arm_suppression(self, alert_ids: set[str]) -> None:
        """Arm the resume suppression set: lines whose ``alert_id`` is in
        the set are counted as already delivered and NOT re-written (the
        set shrinks as ids match, so steady-state cost is an empty-set
        check). service/loop.py fills it by scanning the alert sink past
        the checkpoint's alert cursor before a journal replay."""
        self._suppress |= set(alert_ids)

    def sink_offset(self) -> int:
        """Bytes handed to the sink so far — the alert-delivery cursor
        recorded in checkpoint meta (flush first via :meth:`flush_sink`
        so the cursor equals the on-disk size at a drained instant)."""
        return self._offset

    def flush_sink(self) -> None:
        """Force the sink's stdio buffer to the kernel (best effort —
        failures feed the breaker on the next write, never raise)."""
        if self._fh is None:
            return
        try:
            self._fh.flush()
            self._batches_since_flush = 0
        except OSError:
            pass

    def emit_batch(
        self,
        stream_ids: list[str],
        ts: np.ndarray,
        values: np.ndarray,
        raw: np.ndarray,
        log_likelihood: np.ndarray,
        alerts: np.ndarray,
        group: int | str | None = None,
        tick: int | None = None,
    ) -> int:
        """Write one JSONL line per alerting stream; returns alert count.

        ``group`` + ``tick`` (the group index — possibly epoch-suffixed
        after a quarantine restore, see loop._alert_gid — and the
        group's own tick counter for this row) give every line its
        stable ``alert_id`` (``group:stream:tick``) — the dedupe/replay
        key downstream consumers and crash-resume suppression rely
        on."""
        t0 = time.perf_counter()
        idx = np.nonzero(alerts)[0]
        self.count += idx.size  # crossings scored, sink/suppression aside
        suppressed_this = 0
        attr = None
        if self._attributor is not None:
            # history must advance on every batch, not just alerting ones
            # — but the per-alert decode is only worth computing when a
            # sink will carry it (path=None serves count-only callers)
            attr = self._attributor.update_and_attribute(
                stream_ids, values, idx if self._fh is not None else idx[:0])
        if self._fh is not None and idx.size:
            ts = np.broadcast_to(np.asarray(ts), alerts.shape)
            values = np.asarray(values)
            with_id = group is not None and tick is not None
            # one writelines per batch, not one write per line: the
            # serialization stays per-line (each line is one JSON object)
            # but the file sees a single buffered call
            lines = []
            folds = []
            lat_ts = [] if self._latency is not None else None
            for g in idx:
                aid = f"{group}:{stream_ids[g]}:{int(tick)}" \
                    if with_id else None
                if aid is not None and self._suppress and \
                        aid in self._suppress:
                    # already delivered by the run that crashed: counted,
                    # never duplicated (exactly-once across the crash)
                    self._suppress.discard(aid)
                    self.suppressed += 1
                    suppressed_this += 1
                    self._obs_suppressed.inc()
                    continue
                tf = attr.get(int(g), []) if attr is not None else None
                if self._correlator is not None:
                    folds.append((aid, stream_ids[g], int(ts[g]), tf))
                if lat_ts is not None:
                    lat_ts.append(int(ts[g]))
                lines.append(format_alert_line(
                    aid, stream_ids[g], int(ts[g]), values[g],
                    float(raw[g]), float(log_likelihood[g]),
                    top_fields=tf))
            # fold into the correlator only AFTER the batch reached the
            # sink: a dropped batch (fence lost, breaker open, double
            # write failure) must not seed windows with alert_ids that
            # exist nowhere on the stream — the resume re-fold reads the
            # DISK, and the content-hash incident_id must agree with it.
            # The pre-write offset anchors the correlator's crash-resume
            # sidecar floor (every member of a window lives at/after its
            # window's anchor).
            off0 = self._offset
            if self._safe_write(lines):
                self.written += len(lines)
                if self._correlator is not None:
                    for aid, sid, tsi, tf in folds:
                        self._correlator.observe_alert(aid, sid, tsi,
                                                       top_fields=tf,
                                                       sink_offset=off0)
                if lat_ts:
                    # e2e detect latency at the delivery moment: wall
                    # clock minus each alert's SOURCE timestamp (clamped
                    # >= 0 in the sketch) — pipeline depth, micro-chunk
                    # staleness and backfill hold all show up honestly
                    self._latency.observe_detect(
                        time.time() - np.asarray(lat_ts, np.float64))
        emitted = int(idx.size) - suppressed_this
        if emitted:
            # lines handed toward the sink this call: suppressed ids ride
            # rtap_obs_alerts_suppressed_total instead, never both
            self._obs_alerts.inc(emitted)
        self._obs_emit.observe(time.perf_counter() - t0)
        return int(idx.size)

    def emit_event(self, event: dict) -> None:
        """Write one structured event line (watchdog missed_tick /
        source_starved / checkpoint_stall, quarantine/degradation events,
        membership changes, ...). Events must carry an "event" key so
        downstream consumers can split them from alert records on the
        shared stream. Serialization hoists that key first regardless of
        the caller's dict order: line consumers (live_soak's counter, the
        bitexactness tests' filter) split on the literal prefix
        '{"event"' without parsing every line. Events flush immediately —
        they are rare and tell the incident story."""
        if "event" not in event:
            raise ValueError(f"structured events need an 'event' key: {event}")
        self._obs_events.inc()
        self._safe_write(
            [json.dumps({"event": event["event"], **event}) + "\n"],
            force_flush=True)

    def close(self) -> None:
        if self._fh is not None:
            try:
                self._fh.flush()
                self._fh.close()
            except OSError:
                pass  # the quarantine counters already told the story
            self._fh = None


def iter_alert_records(path: str, offset: int = 0):
    """THE tolerant alert-stream line iterator — one walker for every
    consumer of the shared alert/incident JSONL (the resume suppression
    scan below, scripts/crash_soak.parse_alert_stream and everything
    layered on it, and the incident correlator's resume scan —
    rtap_tpu/correlate/incidents.py), so torn-fragment and event-vs-alert
    semantics can never drift between them.

    Yields ``(kind, record)`` pairs in file order starting at byte
    ``offset``: kind ``"event"`` (a structured line carrying an "event"
    key — dict), ``"alert"`` (a dict, possibly without an alert_id on
    pre-ISSUE-5 streams), or ``"garbage"`` (record is the raw line: a
    torn fragment from a kill mid-write, or a non-object). A missing/
    unreadable file yields nothing — absence is an empty stream, the
    callers' shared convention."""
    try:
        with open(path) as f:
            f.seek(max(0, int(offset)))
            for line in f:
                stripped = line.strip()
                if not stripped:
                    continue
                try:
                    d = json.loads(stripped)
                except ValueError:
                    yield "garbage", line
                    continue
                if not isinstance(d, dict):
                    yield "garbage", line
                    continue
                yield ("event" if "event" in d else "alert"), d
    except OSError:
        return


def scan_alert_ids(path: str, offset: int = 0) -> set[str]:
    """Alert ids already on disk at/after byte `offset` — the resume
    suppression set. The checkpoint meta's alert cursor (recorded at a
    fully-drained save instant) bounds the scan to the post-checkpoint
    window, so resume cost is O(ticks since the last save), not O(file).
    Event lines and torn/unparseable fragments are skipped (a torn line
    never fully delivered its alert — replay re-emits it properly)."""
    ids: set[str] = set()
    for kind, d in iter_alert_records(path, offset):
        if kind != "alert":
            continue
        aid = d.get("alert_id")
        if aid:
            ids.add(aid)
    return ids


def scan_event_ids(path: str, offset: int = 0,
                   events: tuple = ("precursor", "predicted_incident"),
                   ) -> set[str]:
    """Stable EVENT-line alert_ids already on disk at/after `offset` —
    the resume suppression set for id-carrying structured events (the
    predictive ``precursor`` / ``predicted_incident`` lines, whose ids
    are pure functions of (stream, tick) so a journal replay reproduces
    them bit-for-bit). Same walker, same cursor discipline as
    :func:`scan_alert_ids`; alert records and other event kinds are
    skipped."""
    ids: set[str] = set()
    for kind, d in iter_alert_records(path, offset):
        if kind != "event" or d.get("event") not in events:
            continue
        aid = d.get("alert_id")
        if aid:
            ids.add(aid)
    return ids


@dataclass
class ThroughputCounter:
    """Counts scored metrics against wall clock -> metrics/sec/chip."""

    start: float = field(default_factory=time.perf_counter)
    scored: int = 0

    def add(self, n: int) -> None:
        self.scored += int(n)

    @property
    def elapsed(self) -> float:
        return max(time.perf_counter() - self.start, 1e-9)

    @property
    def metrics_per_sec(self) -> float:
        return self.scored / self.elapsed

    def stats(self) -> dict:
        return {
            "scored": self.scored,
            "elapsed_s": round(self.elapsed, 3),
            "metrics_per_sec": round(self.metrics_per_sec, 1),
        }
