"""Per-tick tracing: a near-zero-overhead host span recorder.

The obs registry (obs/metrics.py) answers "how much / how often"; this
module answers "what was happening *around* tick 48120": every loop phase
and per-group dispatch/collect becomes a SPAN (start + duration, tagged
with its tick index — the trace correlation id), and every watchdog /
resilience event becomes an INSTANT on the same timeline, so a
``group_quarantined`` mark lands visually inside the phase span that
raised it. Export is Chrome trace-event JSON (:meth:`chrome_trace`),
loadable directly in ui.perfetto.dev — via ``serve --trace-out FILE`` or
``GET /trace?last=N`` on the obs HTTP server (obs/expo.py).

Design constraints (same bar as the metrics seam — ≤ 1% of the tick
budget, obs/selfbench.py measures it):

- **No locks on the hot path.** Every writer thread owns a private ring
  shard keyed by ``threading.get_ident()`` — the metrics.py cell-sharding
  trick applied to span records. The loop thread and the dispatch-pool
  threads never touch each other's shards; export merges and sorts (cold
  path only).
- **Preallocated, strictly bounded memory.** Each shard is ONE numpy
  structured array of ``capacity`` records (:data:`REC_DTYPE`, 33 bytes
  each) plus a parallel instant-payload ring whose entries are truncated
  to ``max_arg_bytes``. Appending past capacity overwrites the oldest
  record and counts it in :attr:`dropped` — the recorder can run for an
  unbounded soak without growing.
- **Append is a handful of scalar stores.** One interned-name lookup
  (lock-free dict hit after the first use of a name), one structured-row
  tuple store, one integer increment. No allocation after a (thread,
  name) pair's first record.

Span names come from a small vocabulary (the six loop phases, "tick",
event kinds); the intern table is bounded at ``max_names`` and overflow
maps to ``"<other>"`` so a pathological caller cannot grow host memory
through the name channel.

**The seam.** Every host span of the served path — the loop's phases and
per-group children, the stream groups' chunk phases, the ingest handlers,
the AOT warm-up, garbage collections — is written through :class:`span`:
one clock reading at each end serves this recorder's ring (where the
caller hands one) and a ``jax.profiler.TraceAnnotation`` named from
:data:`SPANS` (where a JAX profiler trace is running: ``serve
--jax-trace``, the benchmark's ``--trace 1``), so the loop's ticks, the
groups' phases and the device's ops lie on one clock in one file, joined
by ``tick`` / ``group`` / ``seq``. There is no second span system.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
import weakref

import numpy as np

__all__ = ["TraceRecorder", "REC_DTYPE", "SPANS", "span",
           "install_gc_hook", "uninstall_gc_hook"]

#: The host-span vocabulary — every name :class:`span` writes into a JAX
#: profiler trace, as ``ops/step.py:SCOPES`` is for the device's ops.
#: Identifiers (annotation arguments): ``tick`` = the loop's tick index,
#: ``group`` = the group's FIRST STREAM ID, ``seq`` = the chunk handle's
#: sequence number; so tick -> (group, seq) -> the chunk's four phases ->
#: its execution on the device is one chain. The benchmark's readers match
#: these names (benchmark/layer_metrics/*.json); docs/TELEMETRY.md
#: "Per-tick tracing" lists them for operators.
SPANS = (
    # service/loop.py:live_loop — one tick and its phases (ring: the last
    # component, "tick" / "source" / ...; `sleep` is annotation-only)
    "rtap.loop.tick",
    "rtap.loop.source",
    "rtap.loop.membership",
    "rtap.loop.dispatch",
    "rtap.loop.collect",
    "rtap.loop.emit",
    # ... inside `emit`: the tick's alert decisions to lines in the sink,
    # flushed (`tick`, `lines` = alert lines written; ring: "alert")
    "rtap.loop.alert",
    # ... after it, still inside `emit`, where serve armed them: the tick's
    # health leaves folded into the scorecards (`tick`; ring: "health"), and
    # every group's predict leaves folded into the divergence trajectories
    # plus the blast fuser (`tick`, `precursors`, `incidents` = event lines
    # the tick emitted; ring: "predict")
    "rtap.loop.health",
    "rtap.loop.predict",
    "rtap.loop.checkpoint",
    "rtap.loop.sleep",
    # ... and its per-group children (ring: "dispatch" / "collect" on the
    # group's own track)
    "rtap.loop.group.dispatch",
    "rtap.loop.group.collect",
    # service/registry.py:StreamGroup — the chunk path's four phases
    "rtap.group.stage",
    "rtap.group.enqueue",
    "rtap.group.fetch",
    "rtap.group.likelihood",
    # service/sources.py:TcpJsonlSource — a handler's locked parse of one
    # recv batch (`bytes`, `wait_us`, `values` = values it wrote into the
    # table, `nulls` = values that came as null, counted apart), the loop's
    # locked copy-and-drain (`tick`, `wait_us`, `fields` = values a record)
    "rtap.ingest.feed",
    "rtap.ingest.snapshot",
    # service/aot.py:prewarm — one per program executed (`program`)
    "rtap.aot.warm",
    # one per garbage collection of this process (`generation`, `collected`)
    "rtap.host.gc",
    # ops/resident.py — one per conversion of state leaves between the public
    # layout and the form the device holds them in (`leaves`, `bytes`): set-up,
    # a slot claimed, a checkpoint, a row read; none in a chunk or a live tick
    "rtap.state.relayout",
    # service/checkpoint.py — one group's checkpoint written (the fetch, the
    # re-layout, the files, the swap) or read back onto the device (`group`
    # = the group's first stream id, `bytes` = the state tree's; ring:
    # "checkpoint_save" / "checkpoint_load")
    "rtap.checkpoint.save",
    "rtap.checkpoint.load",
)

#: the name a span takes in a TraceRecorder ring (the names
#: benchmark/traffic_kinds/live.py and the Chrome export's readers know)
_RING_NAME = {name: name.rpartition(".")[2] for name in SPANS}
_RING_NAME["rtap.aot.warm"] = "aot_warm"
_RING_NAME["rtap.host.gc"] = "gc"
_RING_NAME["rtap.checkpoint.save"] = "checkpoint_save"
_RING_NAME["rtap.checkpoint.load"] = "checkpoint_load"

#: one trace record: interned name id, kind (0 span / 1 instant), tick
#: correlation id, start offset vs the recorder epoch (perf_counter
#: seconds), duration (0 for instants), group id (-1 = the loop track)
REC_DTYPE = np.dtype([
    ("name", np.int32),
    ("kind", np.int8),
    ("tick", np.int64),
    ("t0", np.float64),
    ("dur", np.float64),
    ("group", np.int32),
])

_KIND_SPAN = 0
_KIND_INSTANT = 1

#: every TraceRecorder alive: a garbage collection is a loop-track span in
#: each of them (weak: a recorder dies with its owner)
_RECORDERS: "weakref.WeakSet[TraceRecorder]" = weakref.WeakSet()


class _Shard:
    """One writer thread's private ring (no cross-thread writes)."""

    __slots__ = ("recs", "aux", "n", "tick")

    def __init__(self, capacity: int):
        self.recs = np.zeros(capacity, REC_DTYPE)
        self.aux: list = [None] * capacity  # instant payloads (json str)
        self.n = 0  # total appended; ring index = n % capacity
        self.tick = -1  # of the newest record (TraceRecorder.latest_tick)


class TraceRecorder:
    """Lock-free bounded span/instant ring with Chrome trace-event export.

    ``capacity`` is PER WRITER THREAD (the loop thread plus each dispatch
    pool worker gets its own ring); total memory is
    ``n_threads * capacity * (REC_DTYPE.itemsize + max_arg_bytes)`` worst
    case, asserted by tests/unit/test_trace.py.
    """

    def __init__(self, capacity: int = 65536, max_names: int = 1024,
                 max_arg_bytes: int = 256,
                 process_name: str | None = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1; got {capacity}")
        self.capacity = int(capacity)
        self.max_names = int(max_names)
        self.max_arg_bytes = int(max_arg_bytes)
        #: Perfetto process label (fleet stitching keys member traces by
        #: it); settable after construction — serve learns its role late
        self.process_name = process_name
        # perf_counter is the span clock (monotonic, sub-us); the unix
        # anchor lets a reader align the trace with alert-line timestamps
        self.epoch_perf = time.perf_counter()
        self.epoch_unix = time.time()
        #: perf_counter reading shared with a JAX profiler trace
        #: (:meth:`profiler_sync`); None while no such trace was started
        self.profiler_sync_perf: float | None = None
        self._shards: dict[int, _Shard] = {}
        self._names: dict[str, int] = {"<other>": 0}
        self._names_rev: list[str] = ["<other>"]
        self._names_lock = threading.Lock()
        _RECORDERS.add(self)

    # ------------------------------------------------------------ write --
    def _shard(self) -> _Shard:
        tid = threading.get_ident()
        shard = self._shards.get(tid)
        if shard is None:
            shard = self._shards.setdefault(tid, _Shard(self.capacity))
        return shard

    def _name_id(self, name: str) -> int:
        nid = self._names.get(name)
        if nid is not None:
            return nid
        with self._names_lock:
            nid = self._names.get(name)
            if nid is None:
                if len(self._names_rev) >= self.max_names:
                    return 0  # bounded vocabulary: overflow -> "<other>"
                nid = len(self._names_rev)
                self._names_rev.append(name)
                self._names[name] = nid
        return nid

    def add_span(self, name: str, tick: int, t0: float, dur: float,
                 group: int = -1, args_json: str | None = None) -> None:
        """Record one completed span. `t0` is a ``time.perf_counter()``
        reading (the caller already holds one from its own phase
        accounting — re-reading the clock here would double the cost).
        `args_json` (a JSON object, already serialized) rides into the
        Chrome export's ``args``, truncated like an instant's payload."""
        shard = self._shard()
        i = shard.n % self.capacity
        shard.recs[i] = (self._name_id(name), _KIND_SPAN, tick,
                         t0 - self.epoch_perf, dur, group)
        shard.aux[i] = None if args_json is None \
            else args_json[: self.max_arg_bytes]
        shard.tick = tick
        shard.n += 1

    def add_instant(self, name: str, tick: int, fields: dict | None = None,
                    group: int = -1, t: float | None = None) -> None:
        """Record one instant event (watchdog/resilience marks). `fields`
        is serialized now, truncated to `max_arg_bytes` — bounded memory
        beats a perfectly preserved payload (the full event also rides
        the alert JSONL stream). `t` is a ``time.perf_counter()`` reading
        the caller already holds; None reads the clock here."""
        shard = self._shard()
        i = shard.n % self.capacity
        if t is None:
            t = time.perf_counter()
        shard.recs[i] = (self._name_id(name), _KIND_INSTANT, tick,
                         t - self.epoch_perf, 0.0, group)
        aux = None
        if fields:
            try:
                aux = json.dumps(fields)[: self.max_arg_bytes]
            except (TypeError, ValueError):
                aux = repr(fields)[: self.max_arg_bytes]
        shard.aux[i] = aux
        shard.tick = tick
        shard.n += 1

    def profiler_sync(self, t: float) -> None:
        """The one point this timeline shares with a JAX profiler trace:
        `t` is the ``time.perf_counter()`` reading at which the caller
        opened an ``rtap.sync`` ``jax.profiler.TraceAnnotation`` in a trace
        it had just started (``serve --jax-trace``). Recorded as the
        ``profiler_sync`` instant and as ``otherData["profiler_sync_perf"]``
        of :meth:`chrome_trace`: shift the device trace so that its
        ``rtap.sync`` event starts at this instant and the two files lie
        on one clock."""
        self.profiler_sync_perf = t
        self.add_instant("profiler_sync", -1, {"perf_counter": t}, t=t)

    # ------------------------------------------------------------- read --
    def _shard_list(self) -> list[_Shard]:
        for _ in range(8):
            try:
                return list(self._shards.values())
            except RuntimeError:  # dict resize under a brand-new writer
                continue
        return list(dict(self._shards).values())

    def latest_tick(self) -> int:
        """The newest tick any writer thread has recorded under (-1 before
        the first): what a record made outside the loop — a garbage
        collection — is filed under, so tick windows keep or drop it with
        the tick it fell in."""
        return max((s.tick for s in self._shard_list()), default=-1)

    @property
    def total(self) -> int:
        """Records ever appended (spans + instants, including dropped)."""
        return sum(s.n for s in self._shard_list())

    @property
    def dropped(self) -> int:
        """Records overwritten by ring wrap-around."""
        return sum(max(0, s.n - self.capacity) for s in self._shard_list())

    def nbytes(self) -> int:
        """Current preallocated ring memory (structured arrays only; the
        instant-payload rings add at most capacity * max_arg_bytes per
        shard on top). The bound tests assert against this."""
        return sum(s.recs.nbytes for s in self._shard_list())

    def records(self, last_ticks: int | None = None) -> list[dict]:
        """Merged retained records as dicts, sorted by start time.

        `last_ticks=N` keeps only records whose tick is within the last N
        ticks seen across the whole recorder (instants and spans alike);
        records with tick < 0 (unticked) are always kept.
        """
        shards = [(s, min(s.n, self.capacity)) for s in self._shard_list()]
        lo = None
        if last_ticks is not None:
            # window at the numpy layer BEFORE building dicts: a live
            # /trace?last=10 poll must cost O(window), not O(full ring)
            # of GIL-holding dict construction under the serve loop
            hi = max((int(s.recs["tick"][:n].max())
                      for s, n in shards if n), default=None)
            if hi is None:
                return []
            lo = hi - int(last_ticks) + 1
        out = []
        for shard, n in shards:
            if lo is not None:
                ticks = shard.recs["tick"][:n]
                idx = np.nonzero((ticks >= lo) | (ticks < 0))[0]
            else:
                idx = range(n)
            for j in idx:
                r = shard.recs[j]
                rec = {
                    "name": self._names_rev[int(r["name"])],
                    "kind": "span" if r["kind"] == _KIND_SPAN else "instant",
                    "tick": int(r["tick"]),
                    "t0": float(r["t0"]),
                    "dur": float(r["dur"]),
                    "group": int(r["group"]),
                }
                if shard.aux[j] is not None:
                    rec["args_json"] = shard.aux[j]
                out.append(rec)
        out.sort(key=lambda r: r["t0"])
        return out

    def chrome_trace(self, last_ticks: int | None = None) -> dict:
        """The retained timeline as Chrome trace-event JSON (the object
        form: ``{"traceEvents": [...]}``), loadable in ui.perfetto.dev.

        Track layout: tid 0 is the loop thread (phase spans + tick spans
        + untargeted instants); each group `g` gets tid ``g + 1`` for its
        dispatch/collect child spans and group-targeted instants.
        Timestamps are microseconds since the recorder epoch. ``pid`` is
        the REAL process id and a ``process_name`` metadata event labels
        the track — two traces from a leader/standby pair drop onto one
        Perfetto timeline as distinct processes (the otherData epoch
        anchors are what scripts/fleet_trace.py aligns clocks with).
        """
        recs = self.records(last_ticks=last_ticks)
        pid = os.getpid()
        events: list[dict] = [{
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": self.process_name or f"rtap-{pid}"},
        }, {
            "ph": "M", "pid": pid, "tid": 0, "name": "thread_name",
            "args": {"name": "serve loop"},
        }]
        seen_groups: set[int] = set()
        for r in recs:
            g = r["group"]
            tid = 0 if g < 0 else g + 1
            if g >= 0 and g not in seen_groups:
                seen_groups.add(g)
                events.append({
                    "ph": "M", "pid": pid, "tid": tid,
                    "name": "thread_name",
                    "args": {"name": f"group{g}"},
                })
            args: dict = {"tick": r["tick"]}
            if g >= 0:
                args["group"] = g
            if "args_json" in r:
                try:
                    args.update(json.loads(r["args_json"]))
                except ValueError:
                    args["info"] = r["args_json"]
            ev = {
                "name": r["name"],
                "cat": "phase" if g < 0 else "group",
                "pid": pid,
                "tid": tid,
                "ts": round(r["t0"] * 1e6, 3),
                "args": args,
            }
            if r["kind"] == "span":
                ev["ph"] = "X"
                ev["dur"] = round(r["dur"] * 1e6, 3)
            else:
                ev["ph"] = "i"
                ev["s"] = "g"  # global scope: the mark spans all tracks
                ev["cat"] = "event"
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "pid": pid,
                "process_name": self.process_name or f"rtap-{pid}",
                "epoch_unix": self.epoch_unix,
                "epoch_perf": self.epoch_perf,
                "profiler_sync_perf": self.profiler_sync_perf,
                "total_records": self.total,
                "dropped_records": self.dropped,
            },
        }


# ------------------------------------------------------------- the seam --
_trace_me = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def _profiling():
    """``jax.profiler.TraceAnnotation`` while a JAX profiler trace is
    running, else None. JAX is used only where something else has already
    imported it: the cpu-oracle serve path never loads it, and has no
    profiler to write into."""
    global _trace_me
    if _trace_me is None:
        # getattr twice: another thread may be half-way through `import jax`
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _trace_me = getattr(profiler, "TraceAnnotation", None)
        if _trace_me is None:
            return None
    return _trace_me if _trace_me.is_enabled() else None  # JAX's flag, ~20 ns


class span:
    """One host span of the served path, named from :data:`SPANS`.

    A context manager; the loop, whose phases are stretches between clock
    readings it already books (not lexical blocks), uses the pair
    ``sp = span(...).begin()`` / ``t1 = sp.end()`` and reads ``sp.t0``.
    ``perf_counter`` is read once at each end; that reading goes to
    `recorder`'s ring (when one is given: ring name = the vocabulary
    name's last component, ring track = `track`, the group INDEX) and
    brackets the ``TraceAnnotation`` opened when a JAX profiler trace is
    running (arguments: `tick`, `group`, `seq` where given — ``group`` is
    the group's first stream id, what ``rtap.group.*`` carry — and
    `counts`). With neither it is a None check and JAX's flag check.
    """

    __slots__ = ("name", "recorder", "tick", "group", "seq", "track",
                 "counts", "t0", "_ann")

    def __init__(self, name: str, recorder: "TraceRecorder | None" = None,
                 tick: int = -1, group=-1, seq: int = -1, track: int = -1,
                 **counts):
        self.name, self.recorder, self.tick = name, recorder, tick
        self.group, self.seq, self.track = group, seq, track
        self.counts = counts

    def begin(self) -> "span":
        if _gc_hook is None:
            install_gc_hook()  # once a process, on the seam's first use
        annotation = _profiling()
        if annotation is None:
            self._ann = None
        else:
            args = self.counts
            if self.tick != -1:
                args["tick"] = self.tick
            if self.group != -1:
                args["group"] = self.group
            if self.seq != -1:
                args["seq"] = self.seq
            self._ann = annotation(self.name, **args)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def end(self, dur: float | None = None, record: bool = True,
            **counts) -> float:
        """Close the span -> the ``perf_counter`` reading that ended it.
        `dur` overrides the ring's duration (a phase that books less than
        its stretch: nested drains own their own spans), `record=False`
        skips the ring; `counts` known only now (a lock wait, a collected
        count) join the annotation's arguments."""
        t1 = time.perf_counter()
        if self._ann is not None:
            if counts:
                self._ann.set_metadata(**counts)
            self._ann.__exit__(None, None, None)
        if self.recorder is not None and record:
            self.recorder.add_span(
                _RING_NAME[self.name], self.tick, self.t0,
                t1 - self.t0 if dur is None else dur, self.track)
        return t1

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        self.end()


# --------------------------------------------------- garbage collections --
_gc_hook = None  # None: never installed; True: installed; False: removed
_gc_open = None  # (t0, annotation) of the collection in progress
_gc_lock = threading.Lock()


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``rtap.host.gc`` span per collection — an
    annotation under a running profiler, a loop-track ``gc`` span in every
    TraceRecorder alive. Collections do not nest (the collector is not
    re-entrant), so one open slot serves every thread."""
    global _gc_open
    if phase == "start":
        ann = _profiling()
        if ann is not None:
            ann = ann("rtap.host.gc", generation=info["generation"])
            ann.__enter__()
        _gc_open = (time.perf_counter(), ann)
    elif _gc_open is not None:
        t1 = time.perf_counter()
        (t0, ann), _gc_open = _gc_open, None
        if ann is not None:
            ann.set_metadata(collected=info["collected"])
            ann.__exit__(None, None, None)
        for rec in list(_RECORDERS):
            rec.add_span(
                "gc", rec.latest_tick(), t0, t1 - t0,
                args_json='{"generation": %d, "collected": %d}' % (
                    info["generation"], info["collected"]))


def install_gc_hook() -> None:
    """Install the process's one garbage-collection hook (idempotent; the
    seam does it on first use, so every process that writes a span — the
    replay path, which has no TraceRecorder, too — names its own
    collections)."""
    global _gc_hook
    with _gc_lock:
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        _gc_hook = True


def uninstall_gc_hook() -> None:
    """Remove the hook; it stays off until :func:`install_gc_hook`."""
    global _gc_hook, _gc_open
    with _gc_lock:
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        _gc_hook, _gc_open = False, None
