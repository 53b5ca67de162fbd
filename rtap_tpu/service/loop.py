"""Replay and live service loops — the reference's §3.3 tick loop, batched.

`replay_streams` drives a set of equal-length streams through stream groups
as fast as the chip allows (chunked scan dispatches); `live_loop` paces
ticks to a real cadence, polling a callable source each tick — the analog of
the reference's collector.poll() -> per-stream model.run() service loop.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

from rtap_tpu.config import ModelConfig
from rtap_tpu.data.synthetic import LabeledStream
from rtap_tpu.obs import TickWatchdog, get_registry
from rtap_tpu.obs.trace import span
from rtap_tpu.service.alerts import AlertWriter, ThroughputCounter
from rtap_tpu.service.registry import (
    PAD_PREFIX,
    StreamGroup,
    StreamGroupRegistry,
    _Slot as _RegistrySlot,
)

#: bound on remembered rejected-id names under --auto-register (mirrors
#: TcpJsonlSource.MAX_UNKNOWN_TRACKED: an id-spraying producer must not
#: grow a long-lived server's memory); the REJECTED COUNT keeps counting
_MAX_REJECTED_TRACKED = 4096

#: the tick phases the loop accounts wall seconds to; one
#: rtap_obs_phase_seconds histogram per phase (docs/TELEMETRY.md)
_PHASES = ("source", "membership", "dispatch", "collect", "emit", "checkpoint")


def _alert_gid(gi: int, grp):
    """The alert_id group field: the bare group index on a group's
    original timeline, `<gi>.e<epoch>` after a quarantine restore has
    rewound its tick counter (docs/TELEMETRY.md alert schema)."""
    epoch = getattr(grp, "alert_epoch", 0)
    return gi if not epoch else f"{gi}.e{epoch}"


def _scored_counter():
    return get_registry().counter(
        "rtap_obs_scored_total",
        "anomaly-scored (stream, tick) samples emitted — the north-star "
        "metrics counter (live + replay)")


def _sync_source_membership(source, reg) -> None:
    """Push the registry's membership to the source after any change.

    Slot-map-addressed sources (rtap_tpu.ingest.BinaryBatchSource) get
    the (shard, group, slot) map — the registry hands out ADDRESSES,
    not a flat id list (ROADMAP-1); flat-id sources (TcpJsonlSource,
    HttpPollSource) keep their dispatch-order id list. Sources without
    either contract re-derive per tick (the length check is the guard).
    """
    if hasattr(source, "set_slot_map"):
        source.set_slot_map(reg.slot_map())
    elif hasattr(source, "set_ids"):
        source.set_ids(reg.dispatch_ids())


@dataclass
class ReplayResult:
    stream_ids: list[str]
    timestamps: np.ndarray  # [T] int64 (shared clock)
    raw: np.ndarray  # [T, N] f32
    log_likelihood: np.ndarray  # [T, N] f64
    alerts: np.ndarray  # [T, N] bool
    predictions: np.ndarray | None = None  # [T, N] f32 when classifier enabled
    throughput: dict = field(default_factory=dict)


def replay_streams(
    streams: Sequence[LabeledStream],
    cfg: ModelConfig,
    backend: str = "tpu",
    group_size: int | None = None,
    chunk_ticks: int = 64,
    threshold: float = 0.5,
    alert_path: str | None = None,
    learn: bool = True,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    debounce: int = 1,
    seed: int = 0,
    trace=None,
    predict: int = 0,
    predictor=None,
) -> ReplayResult:
    """Replay equal-length streams through grouped models at full speed.

    All streams must share a clock (same length; timestamps of stream 0 are
    used for the result). Groups are sized `group_size` (default: all streams
    in one group) and each chunk of `chunk_ticks` ticks costs one device
    dispatch per group. A stream's `values` are [T], or [T, F] for a model
    of F fields (a row a tick). `seed` is the registry's (group g's state is
    made from ``seed + g``, as `serve` makes it); `trace` (an
    obs.TraceRecorder) takes the checkpoint spans.

    `predict` (a horizon k, serve --predict-horizon) builds the groups with
    the predictor-owned leaves (`pred_ring`, `pred_miss_ewma`, `pred_tick0`)
    in their state, so a fleet warmed here and saved is one a predictive
    `serve --checkpoint-dir` resumes (a checkpoint warmed without them is a
    refused horizon mismatch, `checkpoint.validate_resume`). `predictor` (a
    predict.PredictTracker) folds every collected chunk's predict leaves on
    the group tick clock, as `live_loop` does: the precursor /
    predicted_incident lines the history earns go to the alert sink, their
    ids the ones an uninterrupted serve would have written, and every save
    carries the tracker's latches for the group
    (`PredictTracker.group_state`), so the serving process's fresh tracker
    pages from where this one stood. Off (the default): the state tree, the
    scores and the checkpoints are what they were without the arguments.
    (The model-health reducer is no state: `serve --health` arms it on any
    checkpoint, so a warm-up has nothing to do with it.)

    Crash recovery (SURVEY.md §5 checkpoint/resume as *elastic recovery*):
    with `checkpoint_dir` + `checkpoint_every=k`, each group's full resume
    state (model + likelihood ring + tick count) is saved atomically every k
    collected chunks — the depth-2 pipeline is DRAINED first, because a
    donated in-flight chunk means the device state is already ahead of the
    last collected tick. On a later call with the same `checkpoint_dir`, any
    group with a checkpoint resumes from its recorded tick instead of from
    scratch; ticks before the resume point are left NaN in the result (they
    were scored by the earlier, killed run) and `throughput["resumed_from"]`
    records the boundary. tests/integration/test_crash_resume.py kills a
    replay mid-stream and proves score-identical continuation.
    """
    n = len(streams)
    T = len(streams[0].values)
    for s in streams:
        if len(s.values) != T:
            raise ValueError("replay_streams requires equal-length streams")
    group_size = group_size or n
    ids = [s.stream_id for s in streams]

    if predictor is not None and predictor.horizon != predict:
        raise ValueError(
            f"the predictor folds leaves of horizon {predictor.horizon}; the "
            f"groups are built with predict={predict}")
    reg = StreamGroupRegistry(cfg, group_size=group_size, backend=backend,
                              seed=seed, threshold=threshold, debounce=debounce,
                              predict=predict)
    for sid in ids:
        reg.add_stream(sid)
    reg.finalize()

    values = np.stack([s.values for s in streams], axis=1)  # [T, N(, F)]
    ts = np.stack([s.timestamps for s in streams], axis=1).astype(np.int64)  # [T, N]

    raw = np.full((T, n), np.nan, np.float32)
    loglik = np.full((T, n), np.nan, np.float64)
    alerts = np.zeros((T, n), bool)
    # NaN-fill like raw/loglik: on a resumed run the pre-resume rows were
    # scored by the earlier (killed) process and must read as absent here
    preds = np.full((T, n), np.nan, np.float32) if cfg.classifier.enabled else None
    writer = AlertWriter(alert_path)
    # the predictor's events ride the alert stream, as in live_loop; the
    # sink is this call's writer for this call only (the tracker goes on to
    # the serving loop, which wires its own)
    lend_sink = predictor is not None and predictor.sink is None
    if lend_sink:
        predictor.sink = writer.emit_event
    counter = ThroughputCounter()
    obs_scored = _scored_counter()
    obs_replay_ticks = get_registry().counter(
        "rtap_obs_replay_group_ticks_total",
        "group-ticks collected by replay_streams (sums over groups)")
    resumed_from: dict[str, int] = {}
    suppression_scanned_from: int | None = None  # lowest alert-cursor
    # offset whose tail has been scanned into the suppression set

    # streams were added in order, so group i owns the contiguous slice
    # ids[i*group_size : i*group_size + n_live], at slots 0..n_live-1
    groups_with_work = 0  # groups that will replay at least one tick
    for gi, grp in enumerate(reg.groups):
        ck_path = None
        if checkpoint_dir is not None:
            import os

            from rtap_tpu.service.shardpath import group_checkpoint_path

            ck_path = group_checkpoint_path(checkpoint_dir, gi)
            if os.path.isdir(ck_path):
                from rtap_tpu.service.checkpoint import load_group, validate_resume

                resumed = load_group(ck_path, trace=trace)
                # shared resume-safety gate (stream ids + config + alerting
                # semantics) — one implementation for replay and live serve
                validate_resume(resumed, ck_path, grp)
                if resumed.ticks % chunk_ticks and resumed.ticks < T:
                    raise ValueError(
                        f"checkpoint {ck_path} at tick {resumed.ticks} is not "
                        f"on the chunk grid ({chunk_ticks}); replay it with "
                        "the chunk size it was saved under"
                    )
                grp = reg.groups[gi] = resumed
                resumed_from[f"group{gi}"] = grp.ticks
                ck_off = getattr(grp, "resume_alerts_offset", None)
                if alert_path is not None and ck_off is not None and (
                        suppression_scanned_from is None
                        or ck_off < suppression_scanned_from):
                    # exactly-once across the crash: alert ids the dead
                    # run already delivered past the checkpoints' alert
                    # cursors are suppressed, not duplicated, when the
                    # tail is re-scored. ONE tail scan covers every
                    # group (ids are globally unique); only a torn save
                    # set revealing an even older cursor rescans.
                    from rtap_tpu.service.alerts import (
                        scan_alert_ids, scan_event_ids)

                    writer.arm_suppression(
                        scan_alert_ids(alert_path, ck_off))
                    if predictor is not None:
                        predictor.arm_suppression(
                            scan_event_ids(alert_path, ck_off))
                    suppression_scanned_from = ck_off
                saved = getattr(grp, "resume_predict_state", None)
                if predictor is not None and saved is not None:
                    # the killed run's latches: the re-scored tail pages
                    # (suppressed) exactly where that run paged
                    predictor.restore_group(gi, saved)
        if grp.ticks < T:
            groups_with_work += 1
        # a group resumed AT the end replays zero ticks (all-NaN rows) by
        # design: its scores belong to the earlier run. That is only valid
        # while some OTHER group still has work — the all-complete case is
        # guarded after this loop.
        lo = gi * group_size
        live = grp.n_live
        sids = ids[lo : lo + live]
        # pad slots replay the first live stream's data; their scores are dropped
        gv = np.repeat(values[:, lo : lo + 1], grp.G, axis=1)
        gt = np.repeat(ts[:, lo : lo + 1], grp.G, axis=1)
        gv[:, :live] = values[:, lo : lo + live]
        gt[:, :live] = ts[:, lo : lo + live]
        id_by_slot = sids + [None] * (grp.G - live)

        def collect(bounds, handle):
            t0, t1 = bounds
            r, ll, al = grp.collect_chunk(handle)
            raw[t0:t1, lo : lo + live] = r[:, :live]
            loglik[t0:t1, lo : lo + live] = ll[:, :live]
            alerts[t0:t1, lo : lo + live] = al[:, :live]
            if preds is not None:
                preds[t0:t1, lo : lo + live] = grp.last_predictions[:, :live]
            counter.add((t1 - t0) * live)
            obs_scored.inc((t1 - t0) * live)
            obs_replay_ticks.inc(t1 - t0)
            for i in range(t0, t1):
                # alert_id group:stream:tick — the replay tick IS the
                # group's tick counter (both started at 0 together);
                # epoch-suffixed if the resumed checkpoint carries a
                # rewound-timeline epoch from a live quarantine restore
                writer.emit_batch(sids, gt[i, :live], gv[i, :live],
                                  r[i - t0, :live], ll[i - t0, :live],
                                  al[i - t0, :live],
                                  group=_alert_gid(gi, grp), tick=i)
            if predictor is not None and grp.last_predict is not None:
                # keyed on the group tick (= the replay tick, the chunk's
                # last row), as live_loop's fold: a precursor id is the
                # one a served tick of the same group tick would write
                predictor.fold(gi, grp.last_predict, tick=t1 - 1,
                               ids=id_by_slot)
                predictor.sync_obs()

        # depth-2 pipeline: the device computes chunk t+1 while the host
        # post-processes chunk t (SURVEY.md §7 hard part 3 — overlapped feed)
        pending: deque = deque()
        chunks_done = 0
        for t0 in range(grp.ticks, T, chunk_ticks):
            t1 = min(t0 + chunk_ticks, T)
            handle = grp.dispatch_chunk(gv[t0:t1], gt[t0:t1], learn=learn)
            pending.append(((t0, t1), handle))
            if len(pending) >= 2:
                collect(*pending.popleft())
                chunks_done += 1
            if learn and ck_path is not None and checkpoint_every and \
                    chunks_done and chunks_done % checkpoint_every == 0 \
                    and pending:
                # drain before saving: grp.state must correspond exactly to
                # the last COLLECTED tick or resume would double-step
                while pending:
                    collect(*pending.popleft())
                    chunks_done += 1
                from rtap_tpu.service.checkpoint import save_group

                # drained instant: flush the sink so the alert cursor in
                # meta equals the on-disk size (exactly-once resume)
                writer.flush_sink()
                save_group(grp, ck_path, alerts_offset=writer.sink_offset(),
                           trace=trace, predict_state=_predict_state(predictor, gi))
        while pending:
            collect(*pending.popleft())
            chunks_done += 1
        if learn and ck_path is not None and checkpoint_every and grp.ticks >= T:
            from rtap_tpu.service.checkpoint import save_group

            writer.flush_sink()
            # final state, resumable past the end
            save_group(grp, ck_path, alerts_offset=writer.sink_offset(),
                       trace=trace, predict_state=_predict_state(predictor, gi))
            # (frozen replay never writes — read-only like serve --freeze)
    writer.close()
    if lend_sink:
        predictor.sink = None
    if resumed_from and not groups_with_work:
        # every group's checkpoint is already at tick >= T: the whole replay
        # silently scored ZERO ticks and would return all-NaN (frozen or
        # learning alike). Resume exists to continue interrupted runs;
        # re-scoring a corpus through a trained model is serve --freeze.
        raise ValueError(
            f"checkpoint dir {checkpoint_dir} resumes every group at tick >= "
            f"replay length {T}: nothing left to replay. To re-score this "
            "corpus through the trained model, serve it with --freeze; to "
            "keep learning, replay a longer stream or a fresh checkpoint dir."
        )

    stats = {**counter.stats(), "alerts": writer.count,
             **_device_stats(reg.groups)}
    overflow = _overflow_total(reg.groups)
    if overflow is not None:
        # kernel capacity-overflow observability (learn_cap/col_cap):
        # nonzero means some stream exceeded a static bound and its scores
        # deviate from the oracle — surface it in the replay stats instead
        # of leaving it buried in device state
        stats["tm_overflow_total"] = overflow
    # how near the dense segment pools came to max_segments_per_cell:
    # nonzero tm_full_columns means LRU eviction may have dropped a segment
    stats.update(_capacity_total(reg.groups))
    if resumed_from:
        stats["resumed_from"] = resumed_from
    return ReplayResult(
        stream_ids=ids,
        timestamps=streams[0].timestamps,
        raw=raw,
        log_likelihood=loglik,
        alerts=alerts,
        predictions=preds,
        throughput=stats,
    )


@dataclass
class Resumed:
    """What :func:`resume_registry` loaded."""

    from_ticks: dict[str, int]  # "group<i>" -> the tick its checkpoint holds
    tick_skew: int  # most - fewest ticks over the groups (a torn save set)
    #: the lowest alert cursor the loaded checkpoints carry (None: none does):
    #: sink bytes past it were written after the oldest of them was saved
    alerts_offset: int | None


def resume_registry(reg: StreamGroupRegistry, checkpoint_dir: str,
                    allow_claimed_extras: bool = False, trace=None) -> Resumed:
    """Load every group of a finalized registry that has a checkpoint under
    `checkpoint_dir` (``group<i>``, service/shardpath.py) in the place of
    the group the registry built: the resume of a restarted ``serve
    --checkpoint-dir``. :func:`live_loop` calls it; a caller that needs the
    resumed instances before the loop has them (to wrap them, to read their
    alert cursor) calls it first and then hands the loop no directory.

    Each loaded group passes `checkpoint.validate_resume` against the built
    one (stream ids, config, alerting semantics; `allow_claimed_extras` as
    there), takes its place in ``reg.groups`` and in the registry's slot
    index, and is one `rtap.checkpoint.load` span in `trace`. A checkpoint
    group beyond the built topology is an error, never dropped."""
    import os
    import re

    from rtap_tpu.service.checkpoint import load_group, validate_resume
    from rtap_tpu.service.shardpath import group_checkpoint_path

    groups = reg.groups  # the live list: entries are replaced in place
    from_ticks: dict[str, int] = {}
    for gi, grp in enumerate(groups):
        ck_path = group_checkpoint_path(checkpoint_dir, gi)
        if not os.path.isdir(ck_path):
            continue
        resumed = load_group(ck_path, mesh=grp.mesh, trace=trace)
        # the health flag is serve-run config, not checkpoint state:
        # the resumed instance dispatches the same program variant
        # the built group would have (ISSUE 6)
        resumed.health = getattr(grp, "health", False)
        # claimed extras resume when this run could have claimed them
        # (auto_register) OR when it serves frozen: an elastically-
        # learned fleet must be servable read-only from its own
        # checkpoint (--freeze forbids NEW claims — the footgun — but
        # not reading streams a prior learning run registered)
        validate_resume(resumed, ck_path, grp,
                        allow_claimed_extras=allow_claimed_extras)
        groups[gi] = resumed  # n_live derives from the resumed ids
        # the registry's lookup() index must observe the resumed
        # instance too, not the stale fresh group
        for slot in reg._slots.values():
            if slot.group is grp:
                slot.group = resumed
        # streams the PRIOR run auto-registered (live in the
        # checkpoint, pads in the built group) rejoin the
        # registry's index so routing emits them and re-arriving
        # records aren't re-claimed into duplicate slots
        for si, sid in enumerate(resumed.stream_ids):
            if not sid.startswith(PAD_PREFIX) and sid not in reg:
                reg._slots[sid] = _RegistrySlot(resumed, si)
                reg.version += 1
        from_ticks[f"group{gi}"] = resumed.ticks
    # a checkpoint group BEYOND the built topology must not be
    # silently dropped: a run resumed with a smaller --reserve than
    # the one that learned (e.g. register-then-freeze without
    # repeating --reserve) would lose every stream living in the
    # extra groups — loudly demand a matching topology instead
    stray = sorted(
        d for d in os.listdir(checkpoint_dir)
        if re.fullmatch(r"group\d{4,}", d)
        and int(d[5:]) >= len(groups)
        and os.path.isdir(os.path.join(checkpoint_dir, d))
    ) if os.path.isdir(checkpoint_dir) else []
    if stray:
        raise ValueError(
            f"checkpoint dir {checkpoint_dir} holds {stray} beyond this "
            f"run's {len(groups)} group(s): the prior run had more "
            "claimable capacity. Rerun with the same --reserve/"
            "--group-size so every checkpointed stream resumes")
    # A crash between per-group saves leaves a torn set (groups at
    # different ticks). Live data is NOT tick-indexed (every group
    # scores whatever arrives now) and groups are fully independent,
    # so a behind group merely lost a few ticks of learning — resume
    # anyway, loudly: the skew is warned and exposed in stats.
    # (replay_streams is different: its feed IS tick-indexed, and it
    # resumes each group from its own recorded offset.)
    ticks_seen = {g.ticks for g in groups}
    if len(ticks_seen) > 1:
        import logging

        logging.getLogger(__name__).warning(
            "live_loop: resuming a torn checkpoint set (group ticks %s "
            "— a crash landed between per-group saves); behind groups "
            "lost that many ticks of learning", sorted(ticks_seen))
    cursors = [off for off in (getattr(g, "resume_alerts_offset", None)
                               for g in groups) if off is not None]
    return Resumed(
        from_ticks=from_ticks,
        tick_skew=(max(ticks_seen) - min(ticks_seen)) if from_ticks else 0,
        alerts_offset=min(cursors) if cursors else None)


def live_loop(
    source: Callable[[int], tuple[np.ndarray, int]],
    group: StreamGroup | StreamGroupRegistry,
    n_ticks: int,
    cadence_s: float = 1.0,
    alert_path: str | None = None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    stop_event=None,
    pipeline_depth: int = 1,
    dispatch_threads: int = 1,
    learn: bool = True,
    auto_register: bool = False,
    auto_release_after: int = 0,
    micro_chunk: int = 1,
    chunk_stagger: bool = False,
    chaos=None,
    degradation=None,
    quarantine_restore_after: int = 0,
    alert_flush_every: int = 1,
    aot_warmup: bool = False,
    trace=None,
    flight=None,
    attributor=None,
    journal=None,
    health=None,
    lease=None,
    resume_suppression=None,
    correlator=None,
    latency=None,
    slo=None,
    predictor=None,
    fleet=None,
) -> dict:
    """Paced live scoring: each tick, poll `source(tick) -> (values [G], ts)`,
    score the group(s), emit alerts; sleep off any time left in the cadence
    budget. Returns throughput stats including missed-deadline count — the
    real-time health signal for the 1s-cadence north star.

    `auto_register=True` (with a registry + a source exposing
    `drain_unknown`/`set_ids`, i.e. TcpJsonlSource(track_unknown=True)):
    unknown stream ids arriving on the wire lazily claim free pad slots —
    the reference's model-per-new-metric creation (SURVEY.md C19) without
    recompiling (shapes are static; a claimed slot's model state,
    likelihood probation, and debounce reset — registry.add_stream).
    Capacity = pad slots (group-size rounding + `finalize(reserve=)` +
    released streams); ids beyond capacity are counted in
    `auto_rejected` and not retried.

    `auto_release_after=N` (registry only) is the elastic shrink: a
    stream silent (all-NaN) for N consecutive ticks releases its slot
    back to claimable capacity — a churning monitored cluster (nodes
    leaving) must not exhaust slots. Releases defer to the next tick's
    membership block under the same drain-first rule as claims; a
    released stream that pushes again re-registers as a NEW model (with
    auto_register — a release also clears the rejected-id memory so
    leave-then-join churn converges). N must comfortably exceed ordinary
    outage lengths: the NaN missing-sample semantics deliberately keep
    scoring through gaps, and release discards the model's learned
    context. Source contract under shrink: TcpJsonlSource adapts via
    `set_ids`; a custom callable must size its vector to the registry's
    CURRENT `dispatch_ids()` each tick (a fixed-length callable fails
    the length check loudly on the tick after a release).

    `learn=False` freezes the models (NuPIC `disableLearning()` parity —
    SURVEY §3.2 OPF model surface): SP/TM/classifier state is
    bit-identical after any number of frozen ticks, while raw scores and
    alerts still flow and the anomaly LIKELIHOOD keeps adapting (it is
    the score normalizer, downstream of the model, exactly as in the
    reference's likelihood-outside-the-model layering). Frozen inference
    skips the learning pass, which the silicon ablations put at ~85% of
    the fused step (~155k metrics/s/chip inference-only — SCALING.md).

    `pipeline_depth=2` overlaps the device round trip with the cadence
    sleep: tick k's results are collected and emitted after tick k+1 is
    dispatched, hiding the per-group dispatch+collect latency that
    dominated single-tick dispatches when the chip was not host-local
    (the round trip made the 16x256 production soak miss every 1 s
    deadline at depth 1 — reports/live_soak.json; not re-measured on a
    local chip). Alerts lag one cadence; checkpoint saves
    drain the pipeline first, so nothing is in flight at save time.

    `dispatch_threads=N` issues the per-group dispatch and collect calls
    from a thread pool instead of serially. Depth 2 alone did NOT fix the
    16x256 shape on the remote-attached chip those soaks ran on (p50
    stayed 1.07 s — reports/live_soak_pipelined.json): there each
    dispatch_chunk was itself a blocking ~65 ms call (transfer + launch),
    so 16 groups serialized ~1.04 s of round trips per tick no matter
    when collection happened. Local backends enqueue asynchronously and
    don't need this.
    Threading overlaps the RPCs; groups are independent objects (each
    thread touches exactly one group's state and likelihood ring) and
    emission stays serial in group order after all collects join, so
    output is bit-identical to the serial schedule
    (tests/unit/test_multigroup_serve.py pins it).

    `micro_chunk=M` batches M consecutive ticks into ONE device dispatch
    per group (the chunked scan path, T=M). The 100k-soak forensics
    (reports/live_soak_100k_t48.json and SCALING.md round 5) measured a
    ~12 ms device-side invocation floor PER PROGRAM on that remote-attached
    runtime — at 100 groups that alone is 1.2 s/tick, unfixable by
    threads (48 threads moved nothing) or cadence (k=4 moved nothing).
    Micro-chunking divides the program count by M; the price is alert
    latency: a record is scored up to (M-1) ticks after arrival, plus the
    usual (pipeline_depth-1) chunks of collect lag — total staleness
    <= (pipeline_depth*M - 1) ticks. Deadlines stay per-tick: boundary
    ticks carry the whole chunk's dispatch+collect inside one cadence
    budget. Membership changes, routing rebuilds, and periodic
    checkpoints FORCE a chunk boundary (partial buffers flush, the
    pipeline drains, staggered boundaries re-ramp): claims/releases and
    saves compose with any chunking at the cost of one spiky tick per
    batch — right for churn at tens-of-seconds cadence, wrong for
    per-tick churn (drop micro_chunk there).

    Accepts a single :class:`StreamGroup` or a finalized
    :class:`StreamGroupRegistry`. Measured chip throughput PEAKS at small
    group sizes (SCALING.md bench G-sweep: nothing amortizes with G), so
    at-scale serving is many groups per chip, not one giant group: with a
    registry, each tick dispatches EVERY group before collecting ANY
    (dispatch_chunk/collect_chunk), so the device queue holds all groups'
    step programs back to back while the host does per-group likelihood:
    the interleaved schedule is the production serve path. `source` values
    align with the registry's stream registration order (contiguous
    per-group slices).

    Fault containment (docs/RESILIENCE.md): a dispatch or collect
    exception QUARANTINES that group — it stops being scored, a
    structured ``group_quarantined`` event lands on the alert stream, and
    every other group keeps its cadence (groups are independent; one
    group's wedged device program must not take down the fleet). With
    `checkpoint_dir` and `quarantine_restore_after=N`, a quarantined
    group is re-loaded from its last checkpoint N ticks later
    (``group_restored``); a failed restore gives up loudly
    (``group_restore_failed``) and the group stays quarantined. A source
    that RAISES (vs. returning NaN) is caught: the tick scores a
    whole-vector missing sample and counts ``rtap_obs_source_errors_total``;
    timestamps going backwards are clamped monotonic and counted.
    Checkpoint save failures are per-group events (the atomic save left
    the previous checkpoint intact); 3 consecutive failed rounds open a
    breaker that quarantines checkpointing until its cooldown. The alert
    sink is non-fatal end to end (AlertWriter retry-then-quarantine).

    `degradation` (a resilience.DegradationController) sheds load under
    sustained deadline misses down the declared ladder: learn_thin →
    score_only → tick_widen, with hysteresis, ``degraded``/``recovered``
    events and the ``rtap_obs_degradation_level`` gauge. The controller
    only ever REMOVES learning or widens the effective cadence — scores
    and alerts keep flowing at every level.

    `chaos` (a resilience.ChaosEngine) injects scripted faults at the
    loop's seams — source, per-group dispatch/collect, alert sink file,
    checkpoint saves — for deterministic recovery-path testing
    (scripts/chaos_soak.py, serve --chaos-spec). None = no injection and
    zero hot-path cost.

    `trace` (an obs.TraceRecorder) records the per-tick timeline: every
    phase interval the loop already clocks becomes a span (plus a
    whole-tick span and per-group dispatch/collect child spans from
    inside the fault-capture wrappers), and every watchdog/resilience
    event becomes an instant at the same tick — exported as
    Perfetto-loadable Chrome trace JSON (serve --trace-out, GET /trace).
    The membership and checkpoint spans are positioned at their block
    start with the BOOKED duration (the same drain-exclusion arithmetic
    the phase histograms use), so their on-screen width matches the
    attributed cost, not the raw wall interval. Every span goes through
    the one seam obs/trace.py:span, whose clock readings are the loop's
    own phase accounting: the same reading lands in the ring and brackets
    an `rtap.loop.*` annotation when a JAX profiler trace is running
    (serve --jax-trace), `trace` or no `trace`. None = no ring.

    `flight` (an obs.FlightRecorder) keeps a bounded black-box ring of
    the last N ticks (latency, per-phase deltas, per-group scored
    digest, deadline verdicts, recent events) and auto-dumps an atomic
    postmortem bundle on group quarantine, degradation-level change, or
    a missed-tick burst (docs/POSTMORTEM.md). Dumps are queued mid-tick
    and written AFTER the tick's deadline accounting, so the bundle
    write itself shows up (honestly) in the NEXT tick's budget, never
    inside a phase span.

    `attributor` (a service.attribution.AlertAttributor) adds per-alert
    `top_fields` provenance to alert JSONL lines (serve
    --alert-attribution): the fields whose encoder representation moved
    most, decoded in RDSE key-space (docs/TELEMETRY.md).

    `journal` (a resilience.TickJournal, serve --journal-dir; ISSUE 5
    durability): every ingested tick row is appended to the write-ahead
    journal BEFORE scoring, and on entry any recovered rows past each
    group's checkpoint tick are REPLAYED through the normal scoring
    path — the resumed fleet reaches the crash point bit-identically to
    an uninterrupted run, with already-delivered alert ids suppressed
    via the checkpoint's alert cursor (exactly-once across the crash).
    After each emitted chunk the journal records an alert-delivery
    cursor; after each successful checkpoint round it is compacted to
    the ticks the checkpoints no longer cover. A torn/corrupt journal
    tail was already truncated (counted) when the caller constructed
    the TickJournal — recovery never refuses to start
    (docs/RESILIENCE.md durability section; scripts/crash_soak.py is
    the kill-9 acceptance soak).

    `lease` (a resilience.replicate.Lease, ISSUE 8 hot-standby
    failover): the leadership lease this loop serves under. Freshness
    rides the lease's heartbeat thread (started here if the caller has
    not already); the loop probes ``still_mine()`` at the top of every
    tick, and a probe that finds the lease's fencing epoch advanced
    past ours (a standby promoted while this process was
    paused/partitioned) FENCES the loop — a ``leader_fenced`` event, an
    orderly break (``stats["fenced"] = True``; serve exits
    ``replicate.FENCED_RC``), and the AlertWriter's own fence guard
    refuses any stragglers, so a zombie old leader can never append to
    the alert sink the new leader now owns (docs/RESILIENCE.md failover
    runbook). None = no lease discipline (the single-process default).

    `health` (an obs.HealthTracker, serve --health; ISSUE 6): when the
    groups were built with ``health=True``, every collected chunk
    carries the fused on-device model-health leaf
    (ops/health_tpu.py — segment-pool occupancy, permanence sketch,
    SDR sparsity, predicted->active hit rate, score histogram; pure
    reads, bit-exact-neutral) and the tracker folds it into per-group
    scorecards with EWMA score-drift detection. Health incidents
    (``pool_saturated`` / ``sparsity_collapsed`` / ``score_drift``)
    ride the alert/incident stream like watchdog events and request a
    flight-recorder postmortem dump like a quarantine does. The
    scorecards serve at ``GET /health`` and land in
    ``stats["health"]``. None = leaves (if any) are simply not folded.

    `correlator` (a correlate.IncidentCorrelator, serve --topology;
    ISSUE 9): every alert the writer emits folds into topology-cluster
    correlation windows, and quiesced windows close into cluster-level
    ``incident`` events on the same stream (member alert_ids, blast-
    radius node set, onset tick, attributed fields) — blast-radius
    detection over the per-stream alert stream. The fold keys on the
    stable PR 5 alert_ids and the SOURCE clock, and on resume the
    correlator re-folds the sink tail through the shared tolerant line
    walker, so the incident stream is exactly-once across kill-9/
    journal-replay/failover by construction (scripts/workload_soak.py
    is the acceptance soak; docs/WORKLOADS.md the runbook). None = no
    correlation and zero hot-path cost.

    `latency` (an obs.LatencyTracker, serve --latency; ISSUE 11): the
    detection-latency observability layer. Each tick folds the stage
    waterfall (source ts -> poll -> dispatch -> collect -> emit) into
    bounded windowed quantile sketches and polls the wired lag
    providers (replication-ack lag, incident-close lag); the
    AlertWriter feeds the per-alert end-to-end ``detect`` sketch at
    sink-write time. Pure observation — host wall clocks and
    timestamps already riding the rows, zero extra device↔host
    fetches, and the alert stream + model state are byte/bit-identical
    with the tracker on or off (tests/integration/
    test_latency_serve.py pins it). None = zero hot-path cost.

    `slo` (an obs.SloTracker, serve --slo NAME=TARGET@pQ): operator-
    declared latency SLOs evaluated per tick with fast/slow multi-
    window burn rates; edge-triggered ``slo_burn``/``slo_recovered``/
    ``slo_budget_exhausted`` events ride the alert stream like
    watchdog events, a fast burn requests a flight-recorder postmortem
    dump, and the run's verdict lands in ``stats["slo"]``
    (docs/SLO.md). Requires `latency` (it is the measurement source).

    `predictor` (a predict.PredictTracker, serve --predict; ISSUE 16):
    when the groups were built with ``predict=k``, every collected
    chunk carries the fused on-device predictive-horizon leaf
    (ops/predict_tpu.py — horizon-old predicted-column overlap vs the
    tick's actual input, per-stream divergence EWMA, predicted
    sparsity; pure reads, bit-exact-neutral) and the tracker folds it
    into per-stream divergence trajectories. Edge-triggered
    ``precursor`` events (stable alert ids, predicted lead time) ride
    the alert stream and request flight-recorder dumps; with an
    attached BlastFuser (serve --predict + --topology) the first
    precursor in a topology cluster pages ONE ``predicted_incident``
    with the predicted blast radius. On resume the event ids already
    on disk are re-armed for suppression (service/alerts.
    scan_event_ids) so a journal replay never pages twice. Scorecards
    serve at ``GET /predict`` and land in ``stats["predict"]``. None =
    leaves (if any) are simply not folded.

    Service restarts (SURVEY.md §5 checkpoint/resume, C16): with
    `checkpoint_dir` + `checkpoint_every=k`, every group's full resume
    state is saved atomically every k ticks (the in-flight pipeline is
    drained before each save, so nothing is in flight), and a later call with
    the same dir resumes each group from its recorded tick
    (:func:`resume_registry`) — same
    validation as replay_streams (stream ids, config, alerting semantics
    must match the checkpoint; mismatches are errors, not surprises).
    Saves run inline, so a checkpoint tick may miss its cadence deadline —
    pick `checkpoint_every` with that cost in mind (it is visible in
    `latency_max_ms` and the missed-deadline count). Checkpointing
    requires a registry (the resumed instances replace `group.groups[i]`,
    which a bare StreamGroup argument could not observe).
    """
    if pipeline_depth < 1:
        raise ValueError(f"pipeline_depth must be >= 1; got {pipeline_depth}")
    if micro_chunk < 1:
        raise ValueError(f"micro_chunk must be >= 1; got {micro_chunk}")
    if chunk_stagger and micro_chunk < 2:
        raise ValueError("chunk_stagger needs micro_chunk >= 2")
    if dispatch_threads < 1:
        raise ValueError(f"dispatch_threads must be >= 1; got {dispatch_threads}")
    if quarantine_restore_after < 0:
        raise ValueError(
            f"quarantine_restore_after must be >= 0; got "
            f"{quarantine_restore_after}")
    if quarantine_restore_after and checkpoint_dir is None:
        raise ValueError(
            "quarantine_restore_after needs --checkpoint-dir: restore means "
            "re-loading the group's last checkpoint")
    if isinstance(group, StreamGroupRegistry):
        # _pending empty is NOT finalized: a stream count that is an exact
        # multiple of group_size seals its last group with nothing pending,
        # yet post-finalize membership (claims, releases, version bumps)
        # still requires finalize() — an elastic loop on an unfinalized
        # registry would buffer claims into _pending, invisible to this
        # loop's groups snapshot
        if group._pending or not group._finalized:
            raise ValueError(
                "live_loop needs a finalized registry (finalize() seals the "
                f"last group; {len(group._pending)} streams pending, "
                f"finalized={group._finalized})")
        groups = group.groups  # the live list: resume replaces entries in place
    else:
        if checkpoint_dir is not None:
            raise ValueError(
                "live_loop checkpointing needs a StreamGroupRegistry (a bare "
                "StreamGroup caller could not observe the resumed instances)")
        groups = [group]
    resumed_from: dict[str, int] = {}
    resume_tick_skew = 0
    if checkpoint_dir is not None:
        resumed = resume_registry(group, checkpoint_dir,
                                  allow_claimed_extras=auto_register
                                  or not learn, trace=trace)
        resumed_from, resume_tick_skew = resumed.from_ticks, resumed.tick_skew
        if resumed_from:
            # the source must accept the resumed extras' records and return
            # values in the (possibly grown) dispatch order / slot map
            _sync_source_membership(source, group)
    reg = group if isinstance(group, StreamGroupRegistry) else None

    # Value/emission routing: per group, the live slot indices, their ids,
    # and the group's offset into the source value vector. Live slots are a
    # prefix for a freshly finalized registry, but dynamic membership
    # (claim/release of pad slots — SURVEY.md C19 lazy creation) makes them
    # an arbitrary subset, so routing is index-based, not slicing. Rebuilt
    # only when the registry's membership version changes; each in-flight
    # pipeline entry carries the routing it was dispatched under.
    def _build_routing():
        maps, off = [], 0
        for g in groups:
            slots = g.live_slots()
            maps.append((slots, [g.stream_ids[i] for i in slots], off))
            off += len(slots)
        if predictor is not None and predictor.blast is not None:
            # claimed streams join their cluster's predicted blast
            # radius as soon as they route (idempotent set union)
            predictor.blast.observe_streams(
                sid for _slots, ids, _off in maps for sid in ids)
        return maps, off

    routing, n_expected = _build_routing()
    routing_version = reg.version if reg is not None else 0
    # --- telemetry (rtap_tpu.obs): every hot-path observation below goes
    # through instruments cached here — creation is the cold path, emission
    # is lock-free per-thread cells (docs/TELEMETRY.md catalogs the names)
    obs = get_registry()
    obs_ticks = obs.counter(
        "rtap_obs_ticks_total", "live_loop ticks completed")
    obs_scored = _scored_counter()
    obs_tick_seconds = obs.histogram(
        "rtap_obs_tick_seconds",
        "per-tick host wall seconds (poll -> emit, excl. cadence sleep)")
    obs_phase = {
        p: obs.histogram(
            "rtap_obs_phase_seconds",
            "per-tick wall seconds by loop phase", phase=p)
        for p in _PHASES
    }
    obs_streams = obs.gauge(
        "rtap_obs_streams_active",
        "live (non-pad) stream slots currently routed")
    obs_streams.set(n_expected)
    obs_rebuilds = obs.counter(
        "rtap_obs_routing_rebuilds_total",
        "emission-routing rebuilds after membership version bumps")
    obs_last_tick_wall = obs.gauge(
        "rtap_obs_last_tick_unixtime",
        "wall-clock unix time the last tick completed — the GET /healthz "
        "liveness source (age > stale_after_s reads 503)")
    obs_warm_compiles = obs.counter(
        "rtap_obs_warm_compiles_total",
        "cold (chunk length, group config) programs dispatched serially "
        "to keep compiles single-flight")
    obs_dup_avoided = obs.counter(
        "rtap_obs_dup_compiles_avoided_total",
        "cold programs the pre-(m, config) warm-up keying would have "
        "compiled concurrently in N pool threads (ADVICE r5)")
    obs_trace_records = obs_trace_dropped = None
    if trace is not None:
        # span-ring health as gauges, set once per tick (the recorder has
        # no counters of its own — its hot path is a handful of stores)
        obs_trace_records = obs.gauge(
            "rtap_obs_trace_records",
            "span/instant records appended to the trace ring this run")
        obs_trace_dropped = obs.gauge(
            "rtap_obs_trace_dropped",
            "trace records overwritten by ring wrap-around (grow "
            "--trace-ring if postmortems need deeper history)")
    auto_registered = 0
    auto_rejected_total = 0
    auto_rejected: set = set()  # bounded de-dup memory, not the count
    auto_released = 0
    silent_ticks: dict = {}  # sid -> consecutive all-NaN ticks
    release_pending: set = set()
    if auto_release_after < 0:
        raise ValueError(
            f"auto_release_after must be >= 0; got {auto_release_after}")
    if auto_release_after and reg is None:
        raise ValueError("auto_release_after needs a StreamGroupRegistry")
    if slo is not None and latency is None:
        raise ValueError(
            "slo needs latency: the SLO tracker judges the latency "
            "tracker's observations (serve --slo requires --latency)")
    writer = AlertWriter(alert_path, flush_every=alert_flush_every,
                         attributor=attributor,
                         fence=lease.still_mine if lease is not None
                         else None,
                         correlator=correlator, latency=latency)
    correlator_resume = None
    if correlator is not None:
        # incident correlation (ISSUE 9, rtap_tpu/correlate/): incidents
        # ride the alert stream like watchdog events, and a large-blast
        # incident dumps a postmortem like a quarantine does
        if correlator.sink is None:
            correlator.sink = writer.emit_event
        if correlator.flight is None:
            correlator.flight = flight
        if alert_path is not None:
            # crash/replay safety: re-fold the sink tail BEFORE any
            # replay/live emission — already-delivered alerts re-enter
            # the windows from disk (their replays are suppressed
            # upstream), already-emitted incident ids seed the dedupe
            # set, and incidents that closed pre-crash without their
            # event line landing re-emit (exactly-once incident stream
            # across kill-9). The scan starts at the correlator's
            # persisted sidecar floor, NOT the checkpoints' alert
            # cursors: a checkpoint taken while a window was open has a
            # cursor past that window's earlier members, and a re-fold
            # missing them would hash a divergent incident_id.
            if correlator.sidecar_path is None:
                from rtap_tpu.service.shardpath import alert_sidecar_path

                correlator.sidecar_path = alert_sidecar_path(
                    alert_path, "corr")
            known = [off for off in (
                getattr(g, "resume_alerts_offset", None) for g in groups)
                if off is not None]
            correlator_resume = correlator.resume_from(
                alert_path,
                correlator.resume_scan_offset(min(known) if known else 0))
    if lease is not None:
        # freshness lives on the heartbeat thread (idempotent when the
        # caller already started it); the loop itself only DETECTS the
        # fence via the cached still_mine() probe — a per-tick
        # read+rewrite of the lease file has no place on the hot path
        lease.start_heartbeat()
    if resume_suppression:
        # a promoted standby hands over the alert ids its dead leader
        # delivered for ticks the standby never received: this loop will
        # re-score those ticks live, and the ids must suppress, not
        # duplicate (resilience/replicate.py StandbyFollower._promote)
        writer.arm_suppression(set(resume_suppression))
    fenced = False
    counter = ThroughputCounter()
    # ---- resilience wiring (rtap_tpu.resilience, docs/RESILIENCE.md) ----
    if chaos is not None:
        # injection OUTSIDE the loop's own code: the wrapped source and
        # alert file exercise the real recovery paths from below
        source = chaos.wrap_source(source)
        chaos.wrap_alert_writer(writer)

    def _sync_chaos_routing():
        """Tell the engine which source-vector slice each group reads, so
        group-targeted source faults hit exactly that group's streams.
        Re-synced after every routing rebuild."""
        if chaos is not None:
            chaos.set_group_streams({
                gi: tuple(range(off, off + len(slots)))
                for gi, (slots, _ids, off) in enumerate(routing)})

    _sync_chaos_routing()
    if degradation is not None and degradation.sink is None:
        degradation.sink = writer.emit_event
    if health is not None:
        # same wiring contract as the watchdog/degradation: incidents
        # ride the alert stream, and a health incident is a black-box
        # moment — the flight recorder dumps a postmortem for it, and
        # every bundle's summary embeds the latest scorecards
        if health.sink is None:
            health.sink = writer.emit_event
        if health.flight is None:
            health.flight = flight
        if flight is not None and flight.health_provider is None:
            flight.health_provider = health.snapshot
    slot_ids: dict = {}  # group -> (a routing's ids, its slot -> id list)
    if predictor is not None:
        # same wiring contract as the health tracker: precursor /
        # predicted_incident events ride the alert stream, request
        # postmortem dumps, and every bundle's summary embeds the
        # latest divergence scorecards
        if predictor.sink is None:
            predictor.sink = writer.emit_event
        if predictor.flight is None:
            predictor.flight = flight
        if flight is not None and flight.predict_provider is None:
            flight.predict_provider = predictor.snapshot
        # a fleet resumed from checkpoints pages from where the saving
        # process stood: its latches and open windows, before any journal
        # row is re-folded on top of them
        for gi, g in enumerate(groups):
            saved = getattr(g, "resume_predict_state", None)
            if saved is not None:
                predictor.restore_group(gi, saved)
    if slo is not None:
        # SLO guardrail wiring (ISSUE 11, obs/slo.py): burn events ride
        # the alert stream, a fast burn dumps a postmortem, and the
        # latency tracker feeds it per observation
        if slo.sink is None:
            slo.sink = writer.emit_event
        if slo.flight is None:
            slo.flight = flight
        if latency.slo is None:
            latency.slo = slo
    if latency is not None and flight is not None \
            and flight.latency_provider is None:
        # every postmortem bundle's summary embeds the latest stage
        # waterfall + windowed quantiles (the slo_burn triage surface)
        flight.latency_provider = latency.snapshot
    eff_cadence = cadence_s  # widened by the degradation ladder's level 3
    quarantined: dict[int, dict] = {}  # gi -> {tick, phase, error, restore_at}
    quarantine_log: list[dict] = []  # full quarantine/restore history, in
    # stats: the chaos soak's verification oracle must not depend on the
    # alert stream (whose sink may itself be the faulted component)
    group_scored = [0] * len(groups)  # per-group scored samples (the chaos
    # soak's silent-gap check: a group's count must match its unquarantined
    # tick intervals exactly)
    _res_counters: dict = {}

    def _res_event(kind: str, tick: int, **fields) -> None:
        """Structured resilience event: one registry counter bump per kind
        + one JSONL line on the alert stream (same contract as watchdog
        events; docs/RESILIENCE.md catalogs the vocabulary)."""
        c = _res_counters.get(kind)
        if c is None:
            c = _res_counters[kind] = obs.counter(
                "rtap_obs_resilience_events_total",
                "structured resilience events by kind", event=kind)
        c.inc()
        if trace is not None:
            # same timeline as the phase spans: the quarantine/degrade
            # mark lands visually inside the span that raised it
            trace.add_instant(kind, int(tick), fields,
                              group=int(fields.get("group", -1)))
        if flight is not None:
            flight.record_event({"event": kind, "tick": int(tick), **fields})
        writer.emit_event({"event": kind, "tick": int(tick), **fields})

    obs_groups_quarantined = obs.gauge(
        "rtap_obs_groups_quarantined",
        "stream groups currently quarantined (dispatch/collect fault "
        "isolation)")
    obs_groups_quarantined.set(0)
    # control-plane degradation accounting: only armed when the lease is
    # control-plane-backed (ControlLease exposes ``degraded``); a file
    # lease never counts here
    obs_control_degraded = None
    control_degraded_ticks = 0
    if lease is not None and hasattr(lease, "degraded"):
        obs_control_degraded = obs.counter(
            "rtap_obs_control_degraded_ticks_total",
            "ticks served on the cached control-plane lease while the "
            "plane was unreachable (bounded by the degraded grace "
            "window; >0 after an outage proves no tick stalled)")
    obs_source_errors = obs.counter(
        "rtap_obs_source_errors_total",
        "source callables that RAISED (vs. returning NaN); the tick "
        "scored a whole-vector missing sample instead of dying")
    obs_ts_regressions = obs.counter(
        "rtap_obs_source_time_regressions_total",
        "ticks whose source timestamp went backwards (clamped monotonic)")

    def _quarantine_group(gi: int, tick: int, phase: str, exc: Exception):
        """Isolate a faulted group: it stops being dispatched/collected/
        emitted (and checkpointed — its state may be mid-chunk) while
        every other group keeps its cadence. In-flight handles for the
        group are left uncollected by the quarantine check in
        _collect_tick — after a failed dispatch/collect its seq chain is
        broken anyway."""
        if gi in quarantined:
            return
        info = {"tick": int(tick), "phase": phase,
                "error": f"{type(exc).__name__}: {exc}"}
        if quarantine_restore_after and checkpoint_dir is not None:
            info["restore_at"] = int(tick) + int(quarantine_restore_after)
        quarantined[gi] = info
        quarantine_log.append({"event": "group_quarantined", "group": gi,
                               "tick": int(tick), "phase": phase})
        obs_groups_quarantined.set(len(quarantined))
        _res_event("group_quarantined", tick, group=gi, phase=phase,
                   error=info["error"],
                   streams=int(groups[gi].n_live))
        if flight is not None:
            # the black-box moment: dump a postmortem bundle for this
            # isolation (queued; written after the tick's accounting)
            flight.request_dump("group_quarantined", tick)

    source_error_run = 0  # consecutive source raises (event on the first)
    last_ts_seen = None  # monotonic clamp floor for source timestamps
    ts_regress_run = 0  # consecutive clamped ticks (event on the first)
    # trailing value dims for the NaN substitute when the source raises.
    # Seeded from the model config, NOT discovered from the first good
    # poll: a multivariate source that raises on tick 0 would otherwise
    # get a [G]-shaped substitute where dispatch expects [G, n_fields],
    # and the shape error would quarantine EVERY group permanently.
    _nf = groups[0].cfg.n_fields if groups else 1
    fallback_trailing: tuple = (_nf,) if _nf > 1 else ()
    ck_breaker = None
    ck_quarantine_announced = False
    checkpoint_save_failures = 0
    if checkpoint_dir is not None:
        from rtap_tpu.resilience.policies import CircuitBreaker

        # 3 consecutive failed save ROUNDS quarantine checkpointing (the
        # disk is full — stop paying the drain+fetch+fail cost every
        # cadence); the cooldown admits a probe round later
        ck_breaker = CircuitBreaker(
            fail_threshold=3, cooldown_s=max(30.0, 10 * cadence_s),
            name="checkpoint")

    def _on_save_failure(gi: int, tick: int, exc: Exception) -> None:
        nonlocal checkpoint_save_failures
        checkpoint_save_failures += 1
        _res_event("checkpoint_save_failed", tick, group=gi,
                   error=f"{type(exc).__name__}: {exc}")
    # deadline/starvation/stall events -> registry counters + structured
    # JSONL lines on the alert stream (obs/watchdog.py)
    watchdog = TickWatchdog(cadence_s, registry=obs,
                            event_sink=writer.emit_event,
                            trace=trace, flight=flight)
    missed = 0
    checkpoints_saved = 0
    ticks_run = 0
    last_saved = 0
    latencies = np.empty(n_ticks, np.float64)  # per-tick poll->emit seconds
    # per-phase accounting (100k-soak forensics: the tick period pinned at
    # ~1.4 s independent of stream count AND group count — the breakdown
    # names the binding phase instead of guessing). Wall seconds summed
    # over the run; reported per tick in stats["phase_ms_per_tick"].
    phase_s = {"source": 0.0, "membership": 0.0, "dispatch": 0.0,
               "collect": 0.0, "emit": 0.0, "checkpoint": 0.0}

    # one pool for the whole loop (threads are cheap to keep, expensive to
    # respawn per tick); None = the serial schedule, bit-identical by test
    pool = None
    eff_threads = 1  # effective worker count, reported in stats
    if dispatch_threads > 1 and len(groups) > 1:
        from concurrent.futures import ThreadPoolExecutor

        eff_threads = min(dispatch_threads, len(groups))
        pool = ThreadPoolExecutor(max_workers=eff_threads,
                                  thread_name_prefix="rtap-loop-dispatch")

    cur_tick = 0  # the loop's tick clock, read by the fault-capture paths

    def _try_collect(item):
        """Collect one group's chunk, capturing the fault instead of
        letting it escape a pool thread: (gi, result-or-None, exc-or-None).
        Quarantine itself happens after the join, in the loop thread —
        AlertWriter emission is single-threaded by contract."""
        gi, grp, h = item
        # per-group child span on the group's own track — runs in a pool
        # thread; the recorder's shards are per-thread. `group` / `seq` are
        # what the chunk's own `rtap.group.*` phases carry.
        sp = span("rtap.loop.group.collect", trace, tick=cur_tick,
                  group=grp.stream_ids[0], seq=h["seq"], track=gi).begin()
        try:
            if chaos is not None:
                chaos.on_collect(gi, cur_tick)
            return gi, grp.collect_chunk(h), None
        except Exception as e:  # noqa: BLE001 — any fault isolates the group
            return gi, None, e
        finally:
            sp.end()

    def _collect_tick(ts_rows, value_rows, handles, rmaps, idx=None):
        # collects in parallel (each blocks on its group's device fetch —
        # the per-group RPC on a remote link), emission strictly serial in
        # group order so the alert stream is schedule-independent. `idx`
        # restricts to a subset of groups (chunk_stagger phase classes).
        # Quarantined groups (and handles their failed dispatch left None)
        # are skipped; a collect fault quarantines its group here and the
        # rest of the tick proceeds untouched.
        sel = range(len(groups)) if idx is None else idx
        sp = span("rtap.loop.collect", trace, tick=cur_tick).begin()
        pairs = [(gi, groups[gi], h) for gi, h in zip(sel, handles)
                 if gi not in quarantined and h is not None]
        if pool is None:
            outs = [_try_collect(p) for p in pairs]
        else:
            outs = list(pool.map(_try_collect, pairs))
        t1 = sp.end()
        phase_s["collect"] += t1 - sp.t0
        sp = span("rtap.loop.emit", trace, tick=cur_tick).begin()
        results: dict = {}
        for gi, res, exc in outs:
            if exc is not None:
                _quarantine_group(gi, cur_tick, "collect", exc)
            else:
                results[gi] = res
        scored = 0
        folded = []  # (group, slots, ids) of the groups this tick emitted
        # the tick's alert decisions to lines in the sink, flushed as
        # `alert_flush_every` has it: when this span ends, the tick's lines
        # are as durable as the sink makes them
        sp_alert = span("rtap.loop.alert", trace, tick=cur_tick).begin()
        lines0 = writer.written
        for gi, _grp, _h in pairs:  # pairs preserve group order (emission
            if gi not in results:  # stays schedule-independent)
                continue
            raw, loglik, alerts = results[gi]
            slots, ids, off = rmaps[gi]
            n = len(slots)
            # the group's own tick counter names the rows just collected
            # (collect_chunk already advanced it by the chunk length):
            # alert_id = group:stream:group-tick is stable across restarts
            # and identical to an uninterrupted run's. A mid-run
            # quarantine restore rewinds the counter — its epoch suffix
            # keeps the rewound timeline's ids collision-free.
            grp_tick0 = groups[gi].ticks - len(ts_rows)
            gid = _alert_gid(gi, groups[gi])
            for i, (ts, values) in enumerate(zip(ts_rows, value_rows)):
                writer.emit_batch(ids, np.full(n, ts), values[off:off + n],
                                  raw[i, slots], loglik[i, slots],
                                  alerts[i, slots], group=gid,
                                  tick=grp_tick0 + i)
                counter.add(n)
                scored += n
            group_scored[gi] += len(ts_rows) * n
            folded.append((gi, slots, ids))
        sp_alert.end(lines=writer.written - lines0)
        if health is not None:
            # the tick's fused health leaves into the groups' scorecards
            # (one fold per collected chunk per group; the tracker's own
            # cost is gated by obs/selfbench.py's main)
            with span("rtap.loop.health", trace, tick=cur_tick):
                for gi, _slots, _ids in folded:
                    if groups[gi].last_health is not None:
                        health.fold(gi, groups[gi].last_health, tick=cur_tick)
        if predictor is not None:
            # the tick's fused predict leaves into the per-stream divergence
            # trajectories, and the precursors they fire through the blast
            # fuser; the slot -> id mapping rides the same routing snapshot
            # the emission used, so precursor events page with live stream
            # ids. The fold keys on the GROUP tick (the counter checkpoints
            # carry, = the chunk's last row), NOT the loop-local cur_tick:
            # precursor ids must reproduce across a restart + journal replay
            # for resume suppression
            sp_pred = span("rtap.loop.predict", trace, tick=cur_tick).begin()
            events0 = dict(predictor.events_by_kind)
            for gi, slots, ids in folded:
                if groups[gi].last_predict is None:
                    continue
                held = slot_ids.get(gi)
                if held is None or held[0] is not ids:
                    # once a routing, not once a tick: `ids` is the routing
                    # snapshot's own list until membership changes
                    id_by_slot = [None] * groups[gi].G
                    for s, sid in zip(slots, ids):
                        id_by_slot[s] = sid
                    held = slot_ids[gi] = (ids, id_by_slot)
                predictor.fold(gi, groups[gi].last_predict,
                               tick=groups[gi].ticks - 1, ids=held[1])
            predictor.sync_obs()
            events1 = predictor.events_by_kind
            sp_pred.end(**{
                label: events1.get(kind, 0) - events0.get(kind, 0)
                for label, kind in (("precursors", "precursor"),
                                    ("incidents", "predicted_incident"))})
        obs_scored.inc(scored)
        if journal is not None and pairs:
            # alert-delivery cursor: alerts through this tick have been
            # handed to the sink at this byte offset. A hot standby
            # PRUNES its buffered alert lines on this record (ISSUE 8),
            # so the offset must never point past bytes still sitting
            # in the stdio buffer — flush first (no-op at the
            # flush-per-batch default; with --alert-flush-every N the
            # journal pins an every-tick flush, or a kill would lose
            # alerts the standby already counted as delivered)
            writer.flush_sink()
            journal.append_cursor(journal_base + cur_tick,
                                  writer.sink_offset())
        phase_s["emit"] += sp.end() - t1

    aot_programs = 0
    if aot_warmup:
        # compile every knowable (chunk length, config, learn) program —
        # and the first-claim realignment program — BEFORE tick 0, so no
        # XLA compile can land inside a scored tick (service/aot.py; the
        # 1h 100k soak's 9 missed deadlines were all warm-up compiles)
        from rtap_tpu.service.aot import prewarm

        prewarmed = prewarm(
            groups, micro_chunk, learn, degradation=degradation,
            include_claim=auto_register or any(
                g.free_slot_count() for g in groups), trace=trace)
        aot_programs = len(prewarmed)
    else:
        prewarmed = set()

    warmed: set = set(prewarmed)  # (chunk length m, group config, learn flag)
    # programs already dispatched once: the first dispatch of each PROGRAM
    # runs serially — concurrent cold misses on step.py's compiled-fn
    # lru_cache are not single-flight, so N pool threads would each
    # trace+compile the same program (up to Nx the dominant startup
    # cost). Programs are cached per ModelConfig, and
    # stagger_learn gives groups DISTINCT learn_phase configs — keying by
    # m alone (the pre-r5-ADVICE heuristic) let a later phase class's
    # first flush at an already-seen m cold-compile concurrently in every
    # pool thread. The learn flag is part of the key too: learn=True and
    # learn=False trace distinct programs, and the degradation ladder's
    # score_only step flips it mid-run. chunk_stagger's ramp-in dispatches
    # m=1..M chunks, each a distinct program, so warm-up is per
    # (m, config, learn), never once.
    seen_m: set = set()  # what the old m-only heuristic would have warmed:
    # a cold program at an already-seen m is exactly a duplicate compile
    # the old keying would NOT have serialized — counted as avoided

    # ---- journal recovery + replay (resilience/journal.py, ISSUE 5) ----
    # The write-ahead journal holds every tick row ingested since the
    # oldest live checkpoint. Replay each recovered row past a group's
    # checkpoint tick through the normal per-group dispatch/collect path
    # (m=1 chunks — the same programs, bit-identical results), emitting
    # alerts under the resume suppression set so already-delivered ids
    # are never duplicated and never lost. No cadence: catch-up runs as
    # fast as the chip allows, and its wall cost is reported.
    journal_replay = {"replayed_ticks": 0, "replay_seconds": 0.0,
                      "skipped_rows": 0}
    gpos: list = []
    if journal is not None:
        t_jr0 = time.perf_counter()
        if chaos is not None:
            # replay is RECOVERY, not live serving: no fault window may
            # apply to it (a shifted sink fault at local tick 0 would
            # otherwise drop replayed alerts — permanently, breaking
            # exactly-once). No Fault window can cover tick -1.
            chaos.set_tick(-1)
        # per-group GLOBAL journal cursor: where in the global tick
        # stream each group's checkpoint stopped. Equals the group's own
        # counter on its original timeline, but a mid-run quarantine
        # restore REWINDS the counter while the global clock keeps
        # running — matching rows by grp.ticks would then feed a
        # restored group the wrong rows (or falsely gap-quarantine it),
        # so the save path records the global cursor in meta.
        gpos = [
            grp.resume_journal_tick
            if getattr(grp, "resume_journal_tick", None) is not None
            else grp.ticks
            for grp in groups
        ]
        jrows = [r for r in journal.recovered_ticks
                 if r[0] >= min(gpos, default=0)]
        if journal.truncations or journal.dropped_segments:
            # the torn tail was truncated at construction — say so on
            # the incident stream (counted, never a refusal to start)
            _res_event("journal_tail_truncated", 0,
                       truncations=int(journal.truncations),
                       bytes=int(journal.truncated_bytes),
                       dropped_segments=int(journal.dropped_segments))
        if jrows:
            if alert_path is not None:
                # exactly-once: every alert byte past the checkpoints'
                # alert cursors belongs to the ticks about to be
                # replayed — suppress exactly those ids
                from rtap_tpu.service.alerts import scan_alert_ids

                known_offs = [
                    off for off in (
                        getattr(g, "resume_alerts_offset", None)
                        for g in groups)
                    if off is not None]
                writer.arm_suppression(scan_alert_ids(
                    alert_path, min(known_offs) if known_offs else 0))
                if predictor is not None:
                    # precursor/predicted_incident ids are pure
                    # functions of (stream, group tick), so the replay
                    # below reproduces them — arm the tracker's own
                    # suppression so the replayed folds re-latch state
                    # without paging twice
                    from rtap_tpu.service.alerts import scan_event_ids

                    predictor.arm_suppression(scan_event_ids(
                        alert_path,
                        min(known_offs) if known_offs else 0))
            obs_jr = obs.counter(
                "rtap_obs_journal_replayed_ticks_total",
                "journaled ticks replayed through the scoring path on "
                "resume (crash catch-up)")
            gap_groups: set = set()  # groups whose replay window has a
            # hole (compacted/evicted rows): healing is impossible, and
            # scoring row jt as some earlier tick would SILENTLY corrupt
            # state and alert ids — skip the group loudly instead
            jtable = None  # dispatch table for FRAME records, built once
            from rtap_tpu.resilience.journal import JournaledFrames

            for jt, jts, jvals in jrows:
                if isinstance(jvals, JournaledFrames):
                    # binary-ingest tick: materialize the row by re-
                    # running the ingest scatter over the raw frames
                    # (bit-exact; valid because membership changes
                    # checkpoint + compact at their boundary)
                    if jvals.width != n_expected or reg is None:
                        journal_replay["skipped_rows"] += 1
                        continue
                    from rtap_tpu.ingest.dispatch import (
                        DispatchTable,
                        decode_frames_to_row,
                    )

                    if jtable is None:
                        jtable = DispatchTable.from_registry(reg)
                    jvals = decode_frames_to_row(
                        [jvals.blob], jvals.width, jtable)
                else:
                    jvals = np.asarray(jvals, np.float32)
                if len(jvals) != n_expected:
                    # membership changed between record and resume —
                    # normally impossible: every membership change
                    # checkpoints + compacts at its drained boundary
                    # (the routing-rebuild block below), so a surviving
                    # mismatch means the change ran without a
                    # --checkpoint-dir; skip the row (counted)
                    journal_replay["skipped_rows"] += 1
                    continue
                for gi, grp in enumerate(groups):
                    if gi in quarantined or gi in gap_groups \
                            or gpos[gi] > jt:
                        continue  # this group's checkpoint is already past
                    if jt > gpos[gi]:
                        # QUARANTINE, not just an event: a gap group
                        # resuming live at its stale counter would score
                        # fresh rows as the wrong ticks and reuse
                        # already-delivered alert ids — the exact
                        # corruption the journal exists to prevent
                        gap_groups.add(gi)
                        _quarantine_group(gi, 0, "journal_replay_gap",
                                          RuntimeError(
                                              f"journal gap: group "
                                              f"resumes at global tick "
                                              f"{gpos[gi]} but the "
                                              f"first surviving row is "
                                              f"tick {jt} (compacted/"
                                              "evicted)"))
                        continue
                    slots, g_ids, off = routing[gi]
                    v = np.full((1, grp.G) + jvals.shape[1:], np.nan,
                                np.float32)
                    v[0, slots] = jvals[off:off + len(slots)]
                    t = np.full((1, grp.G), int(jts), np.int64)
                    key = (1, grp.cfg, learn)
                    if key not in warmed:
                        warmed.add(key)
                        obs_warm_compiles.inc()
                    try:
                        r_raw, r_ll, r_al = grp.collect_chunk(
                            grp.dispatch_chunk(v, t, learn=learn))
                    except Exception as e:  # noqa: BLE001 — isolate group
                        _quarantine_group(gi, jt, "journal_replay", e)
                        continue
                    gpos[gi] += 1
                    if health is not None and grp.last_health is not None:
                        # catch-up ticks warm the scorecards/EWMAs too:
                        # the resumed fleet reaches the live edge with
                        # its drift baseline intact, not cold. Tick 0,
                        # like every other replay-time event (_res_event
                        # journal_replayed): the live loop folds with
                        # LOCAL ticks, and a global-tick fold here would
                        # park the flight recorder's per-reason dump
                        # throttle thousands of ticks in the future
                        health.fold(gi, grp.last_health, tick=0)
                    if predictor is not None \
                            and grp.last_predict is not None:
                        # predictor folds key on the GROUP tick — the
                        # counter the checkpoints carry — so a replayed
                        # fold reproduces the pre-crash precursor ids
                        # exactly and the suppression set armed above
                        # catches them (unlike health, whose fold tick
                        # is only dump-throttle metadata)
                        id_by_slot = [None] * grp.G
                        for s, sid in zip(slots, g_ids):
                            id_by_slot[s] = sid
                        predictor.fold(gi, grp.last_predict,
                                       tick=grp.ticks - 1,
                                       ids=id_by_slot)
                    n = len(slots)
                    writer.emit_batch(
                        g_ids, np.full(n, int(jts)), jvals[off:off + n],
                        r_raw[0, slots], r_ll[0, slots], r_al[0, slots],
                        group=_alert_gid(gi, grp), tick=grp.ticks - 1)
                    counter.add(n)
                    obs_scored.inc(n)
                obs_jr.inc()
                if correlator is not None:
                    # the correlation clock advances on the REPLAYED
                    # stream's own timestamps, so every close decision
                    # reproduces the uninterrupted run's bit-for-bit
                    correlator.on_tick(int(jts))
                last_ts_seen = int(jts) if last_ts_seen is None \
                    else max(last_ts_seen, int(jts))
            journal_replay["replayed_ticks"] = \
                len(jrows) - journal_replay["skipped_rows"]
            if gap_groups:
                journal_replay["gap_groups"] = sorted(gap_groups)
            journal_replay["replay_seconds"] = round(
                time.perf_counter() - t_jr0, 4)
            _res_event("journal_replayed", 0,
                       ticks=journal_replay["replayed_ticks"],
                       from_tick=int(jrows[0][0]), to_tick=int(jrows[-1][0]),
                       seconds=journal_replay["replay_seconds"])
        del jrows
        journal.release_recovered()  # a large replay window must not
        # stay resident for the rest of the run (counts live in stats)
    # the run's global tick base: journal records and cursors are indexed
    # past every global position already reached AND every index already
    # on disk (0 on a fresh start). The next_tick floor matters when
    # every group gap-quarantined: appends must never reuse an existing
    # index, so recovery's keep-first-copy dedup stays unambiguous.
    journal_base = max(gpos + [journal.next_tick]) \
        if journal is not None else 0

    def _try_dispatch(gi, grp, v, t, learn_flag):
        """Dispatch one group's chunk, capturing the fault: a raising
        dispatch (device error, wedged RPC surfacing, injected chaos)
        must isolate THAT group, not unwind the tick."""
        sp = span("rtap.loop.group.dispatch", trace, tick=cur_tick,
                  group=grp.stream_ids[0], track=gi).begin()
        seq = -1  # the handle's, once there is one
        try:
            if chaos is not None:
                chaos.on_dispatch(gi, cur_tick)
            handle = grp.dispatch_chunk(v, t, learn=learn_flag)
            seq = handle["seq"]
            return handle, None
        except Exception as e:  # noqa: BLE001 — any fault isolates the group
            return None, e
        finally:
            sp.end(seq=seq)

    def _dispatch_all(value_rows, ts_rows, rmaps, idx=None, learn_flag=None):
        """Dispatch every non-quarantined group in `idx`; returns handles
        ALIGNED WITH `idx` (None for quarantined/faulted groups, which
        _collect_tick skips). A dispatch fault quarantines its group after
        the pool joins (loop-thread-only emission)."""
        if learn_flag is None:
            learn_flag = learn
        sel = list(range(len(groups))) if idx is None else list(idx)
        m = len(value_rows)
        handles: list = [None] * len(sel)
        staged = []  # (handle slot j, gi, grp, v, t)
        for j, gi in enumerate(sel):
            if gi in quarantined:
                continue
            grp = groups[gi]
            slots, _ids, off = rmaps[gi]
            # trailing field axis preserved: values may be [G] or [G, n_fields]
            v = np.full((m, grp.G) + value_rows[0].shape[1:], np.nan,
                        np.float32)
            for i, row in enumerate(value_rows):
                v[i, slots] = row[off:off + len(slots)]
            t = np.repeat(np.asarray(ts_rows, np.int64)[:, None], grp.G,
                          axis=1)
            staged.append((j, gi, grp, v, t))
        faults: list = []
        if pool is None:
            for j, gi, grp, v, t in staged:
                key = (m, grp.cfg, learn_flag)
                if key not in warmed:
                    warmed.add(key)
                    obs_warm_compiles.inc()
                handles[j], exc = _try_dispatch(gi, grp, v, t, learn_flag)
                if exc is not None:
                    faults.append((gi, exc))
            seen_m.add(m)
        else:
            # pooled path: dispatch each COLD (m, config, learn) program
            # serially once (the dispatch call blocks through
            # trace+compile, so the cache is warm before any thread can
            # race it); same-program and warm groups overlap in the pool
            pooled: list = []
            for j, gi, grp, v, t in staged:
                key = (m, grp.cfg, learn_flag)
                if key not in warmed:
                    warmed.add(key)
                    obs_warm_compiles.inc()
                    if m in seen_m:
                        obs_dup_avoided.inc()
                    handles[j], exc = _try_dispatch(gi, grp, v, t, learn_flag)
                    if exc is not None:
                        faults.append((gi, exc))
                else:
                    pooled.append((j, gi, grp, v, t))
            seen_m.add(m)
            if pooled:
                outs = list(pool.map(
                    lambda it: _try_dispatch(it[1], it[2], it[3], it[4],
                                             learn_flag),
                    pooled))
                for (j, gi, _grp, _v, _t), (h, exc) in zip(pooled, outs):
                    handles[j] = h
                    if exc is not None:
                        faults.append((gi, exc))
        for gi, exc in faults:
            _quarantine_group(gi, cur_tick, "dispatch", exc)
        return handles

    # Cross-tick pipeline (pipeline_depth=2): collect tick k-1 AFTER
    # dispatching tick k, so the device round trip — ~65 ms per group per
    # tick on the remote-attached chip of reports/live_soak.json, where it
    # made the 16x256 production soak miss EVERY 1 s deadline (p50 1.07 s)
    # — overlaps the cadence sleep instead of the tick budget.
    # The price is results lagging one tick (alert latency +1 cadence),
    # stated in the stats via "pipeline_depth". Depth 1 keeps the
    # dispatch-collect-emit-same-tick behavior.
    # chunk_stagger: group i belongs to phase class i mod M; each class
    # keeps its own buffer + pipeline and flushes on ITS boundary (class
    # c's first chunk is c+1 rows, then every M) — so each tick dispatches
    # ~1/M of the fleet instead of the whole fleet every M-th tick,
    # leveling the boundary-tick spike the plain micro_chunk path carries
    # (r5 steady soak: 2.8 s of chunk work on one tick = a guaranteed
    # miss). Plain mode is the single class 0.
    n_classes = micro_chunk if chunk_stagger else 1
    class_idx = [
        [i for i in range(len(groups)) if i % n_classes == c]
        for c in range(n_classes)
    ]
    in_flights: list[deque] = [deque() for _ in range(n_classes)]
    chunk_bufs: list[list] = [[] for _ in range(n_classes)]
    first_flush_done = [False] * n_classes

    def _drain_all():
        for c in range(n_classes):
            while in_flights[c]:
                _collect_tick(*in_flights[c].popleft())

    def _align_boundaries():
        """Force a global nothing-buffered, nothing-in-flight instant.

        Rotating per-class boundaries never reach one naturally, but
        membership changes and periodic checkpoints need it (claims
        resize the source vector and reroute emission; saves must match
        the last collected tick). Flush every class's partial buffer,
        drain, and reset the ramp so boundaries re-stagger. Under
        chunk_stagger the partial sizes 1..M are the programs the ramp-in
        already compiled (warm); plain micro_chunk callers normally reach
        here with empty buffers (in-loop membership defers to a natural
        boundary), EXCEPT an out-of-band registry version bump, which
        forces a partial flush — a one-off cold compile of that chunk
        size, single-flighted by the (m, config) warm-up keying — rather
        than dying on the source-length check (ADVICE r5). Cost: one
        spiky tick per membership/checkpoint batch — fine for churn at
        tens-of-seconds cadence, wrong for per-tick churn."""
        for c in range(n_classes):
            if chunk_bufs[c]:
                _flush_class(c)
        _drain_all()
        if chunk_stagger:
            for c in range(n_classes):
                first_flush_done[c] = False

    def _flush_class(c):
        vrows = [b[0] for b in chunk_bufs[c]]
        tsrows = [b[1] for b in chunk_bufs[c]]
        chunk_bufs[c].clear()
        first_flush_done[c] = True
        if not class_idx[c]:
            return  # more classes than groups: nothing to dispatch
        # the degradation ladder removes learning per-chunk at dispatch
        # time (level 1 thins, level >= 2 freezes); it never adds it
        lrn = learn and (degradation is None
                         or degradation.learn_allowed(cur_tick))
        sp = span("rtap.loop.dispatch", trace, tick=cur_tick).begin()
        handles = _dispatch_all(vrows, tsrows, routing, class_idx[c],
                                learn_flag=lrn)
        phase_s["dispatch"] += sp.end() - sp.t0
        in_flights[c].append((tsrows, vrows, handles, routing, class_idx[c]))
        while len(in_flights[c]) >= pipeline_depth:
            _collect_tick(*in_flights[c].popleft())
    try:
        for k in range(n_ticks):
            # orderly shutdown (SIGTERM -> serve's handler sets the event):
            # finish cleanly between ticks, save final state, report stats —
            # an evicted service must not lose since-last-checkpoint learning
            if stop_event is not None and stop_event.is_set():
                break
            if lease is not None:
                # lease-lifecycle events queued by the backend (control
                # plane lost/regained, drain marks) land in the same
                # counters/trace/alert-stream pipe as every other
                # resilience event — the loop stays backend-agnostic
                pop = getattr(lease, "pop_events", None)
                if pop is not None:
                    for ev_kind, ev_fields in pop():
                        _res_event(ev_kind, k, **ev_fields)
                if obs_control_degraded is not None \
                        and getattr(lease, "degraded", False):
                    # the cached-lease path, exercised: this tick runs
                    # without a reachable control plane
                    obs_control_degraded.inc()
                    control_degraded_ticks += 1
            if lease is not None and not lease.still_mine():
                # fenced: a standby promoted past our epoch while this
                # process was paused/partitioned. Stop scoring AND stop
                # emitting (the writer's fence already refuses) — the
                # new leader owns the stream; our unsaved ticks are its
                # journal's to replay, not ours to double-deliver.
                fenced = True
                pop = getattr(lease, "pop_events", None)
                if pop is not None:
                    # the probe that discovered the fence may have queued
                    # its own story (grace exhausted): flush it first
                    for ev_kind, ev_fields in pop():
                        _res_event(ev_kind, k, **ev_fields)
                _res_event("leader_fenced", k,
                           epoch=int(getattr(lease, "epoch", -1)),
                           holder=str(lease.holder() or ""))
                break
            cur_tick = k
            if chaos is not None:
                chaos.set_tick(k)
            # the seam's clock readings are the loop's own: one reading
            # serves the phase accounting, the ring and the annotation
            sp_tick = span("rtap.loop.tick", trace, tick=k).begin()
            sp_phase = span("rtap.loop.membership", trace, tick=k).begin()
            t_start = sp_tick.t0
            t_phase = sp_phase.t0
            scored_tick0 = list(group_scored) if flight is not None else None
            phase_tick0 = dict(phase_s)  # per-tick deltas feed the per-
            # phase histograms at tick end (cumulative sums stay the
            # source of truth for the membership-exclusion arithmetic)
            # membership booking excludes collect/emit/dispatch seconds
            # its drains and forced flushes accrue (those book into their
            # own phases; double-counting would mis-name the binding
            # phase — the instrumentation's job). Captured BEFORE the
            # restore block below: a restore's boundary-align drain books
            # into dispatch/collect, not membership.
            ce_tick0 = (phase_s["collect"] + phase_s["emit"]
                        + phase_s["dispatch"])
            # quarantine auto-restore (docs/RESILIENCE.md): a group whose
            # cooldown elapsed re-loads from its last checkpoint — losing
            # the ticks since that save, keeping every other group's
            # cadence. Books into the membership phase (it IS a membership
            # change: the group's model state is replaced wholesale).
            if quarantined and quarantine_restore_after:
                due = sorted(
                    gi for gi, info in quarantined.items()
                    if info.get("restore_at") is not None
                    and k >= info["restore_at"])
                if due:
                    import os

                    from rtap_tpu.service.checkpoint import (
                        load_group,
                        validate_resume,
                    )
                    from rtap_tpu.service.shardpath import (
                        group_checkpoint_path,
                    )

                    _align_boundaries()
                    restored_any = False
                    for gi in due:
                        ck_path = group_checkpoint_path(
                            checkpoint_dir, gi)
                        old = groups[gi]
                        try:
                            if not os.path.isdir(ck_path):
                                raise FileNotFoundError(
                                    f"no checkpoint at {ck_path} (the group "
                                    "was never saved before its fault)")
                            restored = load_group(ck_path, mesh=old.mesh,
                                                  trace=trace)
                            restored.health = getattr(old, "health", False)
                            validate_resume(
                                restored, ck_path, old,
                                allow_claimed_extras=auto_register
                                or not learn)
                        except Exception as e:  # noqa: BLE001
                            # give up LOUDLY and stop retrying: restore is
                            # best-effort, quarantine is the safe state
                            quarantined[gi]["restore_at"] = None
                            quarantine_log.append(
                                {"event": "group_restore_failed",
                                 "group": gi, "tick": int(k)})
                            _res_event("group_restore_failed", k, group=gi,
                                       error=f"{type(e).__name__}: {e}")
                            continue
                        # the restore REWINDS the group's tick counter:
                        # bump its alert-id epoch so re-used tick
                        # indices never collide with already-delivered
                        # ids on the stream (downstream dedupe contract)
                        restored.alert_epoch = max(
                            restored.alert_epoch,
                            getattr(old, "alert_epoch", 0)) + 1
                        groups[gi] = restored
                        if reg is not None:
                            for slot in reg._slots.values():
                                if slot.group is old:
                                    slot.group = restored
                        del quarantined[gi]
                        restored_any = True
                        quarantine_log.append(
                            {"event": "group_restored", "group": gi,
                             "tick": int(k),
                             "resumed_from_tick": int(restored.ticks)})
                        obs_groups_quarantined.set(len(quarantined))
                        _res_event("group_restored", k, group=gi,
                                   resumed_from_tick=int(restored.ticks))
                    if restored_any:
                        # the restored instances replace groups[gi]: the
                        # routing maps hold per-group slot/id snapshots
                        # and must observe the new objects' membership
                        routing, n_expected = _build_routing()
                        routing_version = reg.version if reg is not None \
                            else 0
                        _sync_chaos_routing()
                        obs_rebuilds.inc()
                        obs_streams.set(n_expected)
                        if reg is not None:
                            _sync_source_membership(source, reg)
            # lazy model creation (serve --auto-register, SURVEY.md C19):
            # unknown ids the TCP listener saw claim free pad slots. The
            # pipeline drains first — membership may only change with
            # nothing in flight (a claimed slot's reset must not race a
            # dispatched-but-uncollected tick's emission routing).
            if auto_register and reg is not None \
                    and (not any(chunk_bufs) or chunk_stagger) \
                    and hasattr(source, "drain_unknown"):
                # filter ids that registered meanwhile (records arriving
                # between a drain and set_ids re-enter the unknown set) and
                # pad-prefixed ids (one malicious "__pad0" record must not
                # crash the server via claim_slot's reserved-prefix guard)
                fresh = [s for s in source.drain_unknown()
                         if s not in auto_rejected and s not in reg
                         and not s.startswith(PAD_PREFIX)]
                if fresh:
                    claimed = False
                    for sid in fresh:
                        if reg.free_slots == 0:
                            # remembered, not retried (capacity is static
                            # until a release) — bounded: an id-spraying
                            # producer must not grow host memory (the same
                            # threat MAX_UNKNOWN_TRACKED guards)
                            auto_rejected_total += 1
                            if len(auto_rejected) < _MAX_REJECTED_TRACKED:
                                auto_rejected.add(sid)
                            continue
                        if not claimed:
                            # membership may only change with nothing
                            # buffered or in flight (a claimed slot's
                            # reset must not race an uncollected tick's
                            # emission routing, and buffered rows carry
                            # the OLD vector length)
                            _align_boundaries()
                            claimed = True
                        reg.add_stream(sid)
                        auto_registered += 1
                    if claimed:
                        _sync_source_membership(source, reg)
            # elastic shrink (serve --auto-release-after): streams silent
            # for N consecutive ticks release their slots back to claimable
            # capacity — a churning monitored cluster (nodes leaving) must
            # not exhaust slots. A released stream that pushes again
            # re-registers as a NEW model (correct lazy semantics: the old
            # temporal context is stale by then anyway). Processed at the
            # top of the tick, like claims, under the same drain rule.
            if release_pending and (not any(chunk_bufs) or chunk_stagger):
                _align_boundaries()
                for sid in release_pending:
                    if sid in reg:
                        reg.remove_stream(sid)
                        silent_ticks.pop(sid, None)
                        auto_released += 1
                release_pending.clear()
                # capacity changed: previously rejected ids deserve a
                # retry (their records will re-surface as unknown) — a
                # leave-then-join churn must converge, not blacklist
                auto_rejected.clear()
                _sync_source_membership(source, reg)
            if reg is not None and reg.version != routing_version:
                # a version bump outside the blocks above (external claim/
                # release between ticks) still needs the aligned instant:
                # buffered rows were polled under the old routing. Plain
                # micro_chunk FORCES a partial flush here (ADVICE r5:
                # deferring to a natural boundary let an external actor
                # resize the source mid-chunk and die on the length check
                # next tick) — the one-off cold compile of the partial
                # chunk size is accepted and single-flighted by the
                # (m, config) warm-up keying above.
                _align_boundaries()
                routing, n_expected = _build_routing()
                routing_version = reg.version
                _sync_chaos_routing()
                obs_rebuilds.inc()
                obs_streams.set(n_expected)
                if journal is not None and checkpoint_dir and learn:
                    # a membership change resizes the journal's row
                    # width: checkpoint NOW (the pipeline is drained)
                    # so the replay window never spans two widths —
                    # otherwise a crash after a claim would skip the
                    # post-claim rows as width-mismatched and gap-
                    # quarantine the fleet on restart
                    writer.flush_sink()
                    _saved_m, failed_m = _save_all(
                        groups, checkpoint_dir, skip=quarantined,
                        chaos=chaos, tick=k,
                        on_failure=lambda gi, e: _on_save_failure(
                            gi, k, e),
                        alerts_offset=writer.sink_offset(),
                        journal_tick=journal_base + ticks_run, trace=trace,
                        predictor=predictor)
                    if not failed_m:
                        checkpoints_saved += 1
                        last_saved = ticks_run
                        if not quarantined:
                            journal.compact(min(
                                (g.ticks for g in groups), default=0))
            _mem_booked = (time.perf_counter() - t_phase) - (
                phase_s["collect"] + phase_s["emit"] + phase_s["dispatch"]
                - ce_tick0)
            phase_s["membership"] += _mem_booked
            # in the ring: positioned at the block start with the BOOKED
            # duration (drains inside the block already own their own spans)
            sp_phase.end(dur=max(0.0, _mem_booked),
                         record=_mem_booked > 1e-6)
            sp_phase = span("rtap.loop.source", trace, tick=k).begin()
            now = sp_phase.t0
            tick_frames = None  # raw binary ingest frames (journal path)
            try:
                values, ts = source(k)
            except Exception as e:  # noqa: BLE001
                # a RAISING source (connection drop, garbage payload the
                # adapter didn't absorb) must not kill scoring: the tick
                # becomes a whole-vector missing sample — the NaN path the
                # encoder already handles — counted, and evented on the
                # first raise of a consecutive run (the counter keeps
                # counting; the starvation watchdog narrates a long outage)
                obs_source_errors.inc()
                source_error_run += 1
                if source_error_run == 1:
                    _res_event("source_error", k,
                               error=f"{type(e).__name__}: {e}")
                values = np.full((n_expected,) + fallback_trailing, np.nan,
                                 np.float32)
                # stay on the SOURCE's timeline, not the host's: a wall
                # clock ahead of the feed's timestamps would pin the
                # monotonic clamp below and freeze ts for the whole run
                ts = last_ts_seen if last_ts_seen is not None \
                    else int(time.time())
            else:
                source_error_run = 0
                if journal is not None and hasattr(source,
                                                   "take_tick_frames"):
                    # only a SUCCESSFUL poll may journal raw frames —
                    # the fallback NaN tick below must journal as the
                    # full-width NaN row it actually scored
                    tick_frames = source.take_tick_frames()
            phase_s["source"] += sp_phase.end() - now
            # the poll-done wall instant anchors the tick's ingest-lag
            # measurement (source ts -> loop); perf_counter has no epoch
            lat_poll_wall = time.time() if latency is not None else 0.0
            values = np.asarray(values, np.float32)
            watchdog.observe_source(k, values)
            if len(values) != n_expected:
                raise ValueError(
                    f"source returned {len(values)} values for {n_expected} "
                    "live streams (alignment with registration order is load-"
                    "bearing — a silent mismatch would misroute streams)")
            fallback_trailing = values.shape[1:]
            # timestamps must not run backwards into the models' date
            # encodings (a misbehaving exporter clock): clamp monotonic
            # non-decreasing, count, and event the first regression of a run
            ts = int(ts)
            if last_ts_seen is not None and ts < last_ts_seen:
                obs_ts_regressions.inc()
                if ts_regress_run == 0:
                    _res_event("source_time_regression", k, ts=ts,
                               clamped_to=last_ts_seen)
                ts_regress_run += 1
                ts = last_ts_seen
            else:
                ts_regress_run = 0
                last_ts_seen = ts
            if journal is not None:
                # the write-ahead moment: the row is durable (flushed to
                # the kernel; fsync per policy) BEFORE any scoring — a
                # death past this point replays this tick on restart.
                # Binary ingest ticks journal their RAW wire frames
                # (10 B/row that actually arrived) instead of the
                # re-encoded full-width vector (ISSUE 7)
                if tick_frames is not None:
                    journal.append_tick_frames(journal_base + k, ts,
                                               len(values), tick_frames)
                else:
                    journal.append_tick(journal_base + k, ts, values)
            if chaos is not None:
                # proc_exit fires here — after the row is journaled, so
                # a restart's resume base is unambiguously past it
                chaos.on_tick_ingested(k)
            if auto_release_after:
                # consecutive-silence accounting over THIS tick's values;
                # releases defer to the next tick's membership block (this
                # tick's value vector still matches the current routing)
                nan = np.isnan(values)
                nan_mask = nan if nan.ndim == 1 else \
                    nan.reshape(len(values), -1).all(axis=1)
                for slots, ids, off in routing:
                    for j, sid in enumerate(ids):
                        if nan_mask[off + j]:
                            n = silent_ticks.get(sid, 0) + 1
                            silent_ticks[sid] = n
                            if n >= auto_release_after:
                                release_pending.add(sid)
                        else:
                            silent_ticks.pop(sid, None)
            # held across ticks (micro_chunk) and across collects
            # (depth >= 2): a source reusing a preallocated buffer must not
            # corrupt the emitted values column
            row = (values.copy() if pipeline_depth > 1 or micro_chunk > 1
                   else values, ts)
            for c in range(n_classes):
                chunk_bufs[c].append(row)
                # staggered first flush at c+1 rows tiles class boundaries
                # across ticks; afterwards every class flushes at M rows
                target = micro_chunk if (first_flush_done[c]
                                         or not chunk_stagger) else c + 1
                if len(chunk_bufs[c]) >= target or k + 1 == n_ticks:
                    _flush_class(c)
            if correlator is not None:
                # after this tick's emission: close quiesced windows on
                # the SOURCE clock (ts is the clamped tick timestamp, so
                # a journal replay reproduces every close decision).
                # Alerts lagging in the pipeline carry their own older
                # ts — size --correlate-window above the staleness bound
                # (pipeline_depth * micro_chunk ticks, docs/WORKLOADS.md).
                # The writer offset lets an all-windows-closed tick
                # advance the crash-resume sidecar floor to the sink end.
                correlator.on_tick(ts, tick=k,
                                   sink_offset=writer.sink_offset())
            ticks_run = k + 1
            if learn and checkpoint_every and checkpoint_dir \
                    and (not any(chunk_bufs) or chunk_stagger) \
                    and ticks_run - last_saved >= checkpoint_every \
                    and (lease is None or lease.still_mine()):
                # (the lease gate keeps a paused old leader that woke
                # MID-tick from clobbering the promoted standby's
                # checkpoints before the top-of-tick fence check fires)
                # nothing may be in flight at save time: drain the pipeline
                # first (same rule as replay's drain-before-save). The
                # trigger is due-since-last-save, not a modulus: with
                # micro_chunk > 1 boundaries land only at multiples of M,
                # and `ticks_run % checkpoint_every == 0` would silently
                # degrade the cadence to lcm(M, checkpoint_every)
                if ck_breaker.allow():
                    ck_quarantine_announced = False
                    sp_phase = span("rtap.loop.checkpoint", trace,
                                    tick=k).begin()
                    now = sp_phase.t0
                    ce0 = (phase_s["collect"] + phase_s["emit"]
                           + phase_s["dispatch"])
                    ck0 = phase_s["checkpoint"]
                    _align_boundaries()
                    # drained instant: flush the sink so each meta's
                    # alert cursor equals the on-disk size (exactly-once
                    # resume suppression reads from it)
                    writer.flush_sink()
                    _saved, failed = _save_all(
                        groups, checkpoint_dir, skip=quarantined,
                        chaos=chaos, tick=k,
                        on_failure=lambda gi, e: _on_save_failure(gi, k, e),
                        alerts_offset=writer.sink_offset(),
                        journal_tick=journal_base + ticks_run
                        if journal is not None else None, trace=trace,
                        predictor=predictor)
                    phase_s["checkpoint"] += (time.perf_counter() - now) - (
                        phase_s["collect"] + phase_s["emit"]
                        + phase_s["dispatch"] - ce0)
                    sp_phase.end(dur=max(0.0, phase_s["checkpoint"] - ck0))
                    watchdog.observe_checkpoint(
                        k, phase_s["checkpoint"] - ck0)
                    if failed:
                        # per-group events already emitted; the breaker
                        # decides when a failing disk stops being worth
                        # the drain+fetch cost every round. last_saved is
                        # NOT advanced: the round remains due (retried
                        # next tick until the breaker opens), and the
                        # end-of-run best-effort save must still fire —
                        # advancing it would silently mark failed progress
                        # as saved and suppress both.
                        ck_breaker.record_failure()
                    else:
                        ck_breaker.record_success()
                        checkpoints_saved += 1
                        last_saved = ticks_run
                        if journal is not None and not quarantined:
                            # ticks below every live checkpoint can never
                            # be replayed again — keep the journal
                            # O(checkpoint_every) ticks on disk. With a
                            # group QUARANTINED, compaction pauses: its
                            # restore source is an older checkpoint whose
                            # replay window must stay on disk (a crash-
                            # restart replays it back to health)
                            journal.compact(min(
                                (g.ticks for g in groups), default=0))
                else:
                    # checkpointing quarantined: saves are skipped (and
                    # said so, once per episode) until the breaker's
                    # cooldown admits a probe round. Scoring never
                    # pauses; the round stays due so the probe fires at
                    # the first allowed tick.
                    if not ck_quarantine_announced:
                        ck_quarantine_announced = True
                        _res_event(
                            "checkpoint_quarantined", k,
                            consecutive_failures=
                            ck_breaker.consecutive_failures,
                            cooldown_s=ck_breaker.cooldown_s)
            elapsed = sp_tick.end() - t_start
            latencies[k] = elapsed
            obs_ticks.inc()
            obs_last_tick_wall.set(time.time())
            obs_tick_seconds.observe(elapsed)
            for p in _PHASES:
                obs_phase[p].observe(phase_s[p] - phase_tick0[p])
            if trace is not None:
                obs_trace_records.set(trace.total)
                obs_trace_dropped.set(trace.dropped)
            missed_this = watchdog.observe_tick(k, elapsed)
            if missed_this:
                missed += 1
            if degradation is not None:
                # the controller reacts to the deadline verdicts the
                # watchdog just judged; its tick_widen step changes the
                # effective cadence BOTH sides measure against from here on
                _deg_level0 = degradation.level
                degradation.observe(k, missed_this)
                if flight is not None and degradation.level != _deg_level0:
                    # every ladder move (either direction) is a black-box
                    # moment: capture the window that caused it
                    flight.request_dump("degradation_level_change", k)
                new_cadence = cadence_s * degradation.cadence_scale
                if new_cadence != eff_cadence:
                    eff_cadence = new_cadence
                    watchdog.set_cadence(eff_cadence)
            if latency is not None:
                # fold the tick's stage waterfall + lag probes; the SLO
                # evaluation runs after, so any slo_burn dump it queues
                # is flushed by THIS tick's flush_pending below
                latency.record_tick(
                    k, ts, {p: phase_s[p] - phase_tick0[p]
                            for p in _PHASES},
                    elapsed, poll_wall=lat_poll_wall, source=source)
                if slo is not None:
                    slo.on_tick(k)
            if fleet is not None:
                # one guarded int store; the fleet pushes themselves run
                # on the publisher's own thread, never on the tick path
                fleet.note_tick(k)
            if flight is not None:
                flight.record_tick(
                    k, elapsed,
                    {p: phase_s[p] - phase_tick0[p] for p in _PHASES},
                    [a - b for a, b in zip(group_scored, scored_tick0)],
                    missed_this)
                # queued dumps (quarantine/degradation/miss burst) write
                # HERE — after deadline accounting, before the sleep, so
                # the cost never lands inside a phase span; the budget
                # below is recomputed from the wall clock, so a dump
                # consumes this tick's remaining SLEEP, not the cadence
                # (pacing stays honest — the next tick starts on time or
                # immediately, never late-but-unreported)
                flight.flush_pending()
            # a recovery transition can shrink eff_cadence below this
            # tick's elapsed — clamp, don't feed time.sleep a negative.
            # Wall-clock based (not `elapsed`): post-accounting work
            # (bundle dumps above) must shorten the sleep, not stretch
            # the tick period silently past the cadence.
            budget = max(0.0, eff_cadence - (time.perf_counter() - t_start))
            if not missed_this and k + 1 < n_ticks:
                # annotation only: in a profiler trace the device's idle
                # time between ticks has a name; the ring keeps ticks
                with span("rtap.loop.sleep", tick=k):
                    if stop_event is not None:
                        stop_event.wait(budget)  # a shutdown signal ends it
                    else:
                        time.sleep(budget)
        for c in range(n_classes):
            if chunk_bufs[c]:
                # early stop mid-chunk: score what was ingested
                _flush_class(c)
        _drain_all()  # every dispatched tick is collected + emitted
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
        if flight is not None:
            # a quarantine raised by the final drain (or an early stop)
            # queued its dump after the last in-loop flush — write it
            flight.flush_pending()
    if learn and checkpoint_dir and not fenced \
            and (lease is None or lease.still_mine()) \
            and (ticks_run > last_saved
                 or journal_replay["replayed_ticks"] > 0):
        # (a FENCED leader skips the final save too: the shared
        # checkpoint dir belongs to the promoted standby now, and a
        # zombie's save would clobber the new timeline's resume state)
        # final state on exit (clean or stopped), like replay_streams — a
        # resume must not lose already-learned ticks. Gated on the dir
        # alone: checkpoint_every=0 with a dir means "save only on exit".
        # Frozen serving (learn=False) never writes: --checkpoint-dir is
        # read-only there (resume the trained model, mutate nothing) — a
        # frozen replica must not clobber the golden checkpoint with
        # advanced tick counters, and two frozen replicas may share a dir.
        # Bypasses the checkpoint breaker (one last best-effort save);
        # failures are evented and counted, never raised over a finished
        # run — each group's previous checkpoint is intact by atomicity.
        writer.flush_sink()
        _saved, failed = _save_all(
            groups, checkpoint_dir, skip=quarantined, chaos=chaos,
            tick=ticks_run,
            on_failure=lambda gi, e: _on_save_failure(gi, ticks_run, e),
            alerts_offset=writer.sink_offset(),
            journal_tick=journal_base + ticks_run
            if journal is not None else None, trace=trace,
            predictor=predictor)
        if not failed:
            checkpoints_saved += 1
            if journal is not None and not quarantined:
                # same pause-while-quarantined rule as the in-loop site
                journal.compact(min((g.ticks for g in groups), default=0))
    writer.close()
    lat = {}
    if ticks_run > 0:
        used = latencies[:ticks_run]
        lat = {
            f"latency_p{p}_ms": round(float(np.percentile(used, p)) * 1e3, 3)
            for p in (50, 90, 99)
        }
        lat["latency_max_ms"] = round(float(used.max()) * 1e3, 3)
    extra = {}
    if checkpoint_dir is not None:
        extra["checkpoints_saved"] = checkpoints_saved
        if resumed_from:
            extra["resumed_from"] = resumed_from
            extra["resume_tick_skew"] = resume_tick_skew
    if ticks_run < n_ticks:
        extra["stopped_early"] = True
        extra["ticks_requested"] = n_ticks
    if fenced:
        # the fence story lives in stats + counters, never on the sink
        # (the whole point is that a fenced leader appends NOTHING)
        extra["fenced"] = True
        extra["fenced_line_drops"] = writer.fenced_drops
    if obs_control_degraded is not None:
        extra["control_degraded_ticks"] = control_degraded_ticks
    if ticks_run > 0:
        extra["phase_ms_per_tick"] = {
            k: round(v / ticks_run * 1e3, 2) for k, v in phase_s.items()}
    # resilience accounting (docs/RESILIENCE.md): per-group scored counts
    # are the chaos soak's silent-gap oracle — a group's count must equal
    # its unquarantined tick span exactly, or streams silently stopped
    extra["scored_by_group"] = [int(x) for x in group_scored]
    if quarantined:
        extra["quarantined"] = {
            f"group{gi}": {kk: vv for kk, vv in info.items()
                           if kk != "restore_at"}
            for gi, info in sorted(quarantined.items())}
    if quarantine_log:
        extra["quarantine_log"] = quarantine_log
    if degradation is not None:
        extra["degradation"] = degradation.stats()
    if checkpoint_save_failures:
        extra["checkpoint_save_failures"] = checkpoint_save_failures
    if chaos is not None:
        extra["chaos_injected"] = len(chaos.injected)
    if journal is not None:
        # the durability artifact: what was recovered/replayed, what the
        # torn-tail truncation cost, what exactly-once suppressed
        extra["journal"] = {**journal.stats(), **journal_replay,
                            "suppressed_alerts": writer.suppressed}
    if flight is not None:
        extra["postmortem"] = flight.stats()
    if health is not None:
        # the model-health artifact: scorecard rollup + incident counts
        extra["health"] = health.stats()
    if predictor is not None:
        # the predictive-horizon artifact: divergence rollup, precursor/
        # predicted_incident counts, replay-suppression accounting
        extra["predict"] = predictor.stats()
    if correlator is not None:
        # the correlation artifact: incidents emitted, windows expired,
        # resume re-fold summary (docs/WORKLOADS.md incident schema)
        extra["incidents"] = correlator.stats()
        if correlator_resume is not None:
            extra["incidents"]["resume"] = correlator_resume
    if latency is not None:
        # the detection-latency artifact: per-stage quantiles, the last
        # waterfall, lag gauges (docs/SLO.md triage order starts here)
        extra["latency"] = latency.stats()
    if slo is not None:
        # the SLO verdict the soaks commit: met/bad-frac/budget per
        # declared SLO plus burn-episode counts
        extra["slo"] = slo.verdict()
    if aot_warmup:
        extra["aot_programs_compiled"] = aot_programs
        # cold programs the loop still had to single-flight AFTER the AOT
        # pass — the integration test pins this at zero; nonzero means the
        # knowable-program enumeration missed a shape (a bug, surfaced
        # here instead of as a tail-latency spike)
        extra["cold_compiles_after_warmup"] = max(
            0, len(warmed) - len(prewarmed))
    return {**counter.stats(), "alerts": writer.count, "missed_deadlines": missed,
            "ticks": ticks_run, "cadence_s": cadence_s, "n_groups": len(groups),
            "pipeline_depth": pipeline_depth, "micro_chunk": micro_chunk,
            "chunk_stagger": chunk_stagger,
            "learn": learn,
            **({"auto_registered": auto_registered,
                "auto_rejected": auto_rejected_total} if auto_register else {}),
            **({"auto_released": auto_released} if auto_release_after else {}),
            # effective value: 1 when the pool was never created (single
            # group), so soak reports can't claim threading they didn't get
            "dispatch_threads": eff_threads,
            **extra, **lat, **_device_stats(groups)}


def _predict_state(predictor, gi: int) -> dict | None:
    """What group `gi`'s checkpoint carries of the predictive tracker (None
    where no tracker is armed, or it has folded nothing of the group yet)."""
    return None if predictor is None else predictor.group_state(gi)


def _save_all(groups, checkpoint_dir: str, skip=(), chaos=None, tick: int = 0,
              on_failure=None, alerts_offset: int | None = None,
              journal_tick: int | None = None, trace=None,
              predictor=None) -> tuple[int, int]:
    """One atomic per-group save per group dir (group{i:04d}), each with the
    predictive tracker's part of its group where `predictor` is armed.

    Quarantined groups (`skip`) are NOT saved: their state may be
    mid-chunk and their last good checkpoint is the restore source.
    Failures are contained per group — reported through `on_failure`,
    never raised — because a full disk must not kill scoring, and
    save_group's temp-sibling atomicity guarantees the previous
    checkpoint is still intact after any failure. Returns
    (saved, failed) counts."""
    from rtap_tpu.service.checkpoint import save_group
    from rtap_tpu.service.shardpath import group_checkpoint_path

    saved = failed = 0
    for gi, grp in enumerate(groups):
        if gi in skip:
            continue
        try:
            if chaos is not None:
                chaos.on_checkpoint_save(gi, tick)
            save_group(grp, group_checkpoint_path(checkpoint_dir, gi),
                       alerts_offset=alerts_offset,
                       journal_tick=journal_tick, trace=trace,
                       predict_state=_predict_state(predictor, gi))
            saved += 1
        except Exception as e:  # noqa: BLE001 — contained per group
            failed += 1
            if on_failure is not None:
                on_failure(gi, e)
    return saved, failed


# rtap: host-boundary — end-of-run stats fetch of two scalar-per-stream
# counters; runs once per serve exit, never on the hot path, and a mesh
# gather of [G] i32 leaves is bytes, not state
def _overflow_total(groups) -> int | None:
    """Sum the per-stream kernel overflow counter (tm_overflow) across
    device groups; None for CPU-oracle groups (the oracle has no
    capacity bounds to overflow)."""
    total = 0
    saw_device = False
    for grp in groups:
        if grp.backend != "tpu":
            continue
        saw_device = True
        total += int(np.asarray(grp.state["tm_overflow"]).sum())
    return total if saw_device else None


def _capacity_total(groups) -> dict:
    """Segment-pool headroom over all groups (registry.segment_capacity):
    ``tm_full_cells`` and ``tm_full_columns`` summed, ``tm_max_segments``
    the largest count on any cell."""
    per_group = [g.capacity_stats() for g in groups]
    return {
        "tm_full_cells": sum(c["full_cells"] for c in per_group),
        "tm_full_columns": sum(c["full_columns"] for c in per_group),
        "tm_max_segments": max(
            (c["max_segments_on_a_cell"] for c in per_group), default=0),
    }


def _device_stats(groups) -> dict:
    """Where the run's device groups ran and what they hold there, for the
    stats line: ``platform``/``device_kind``/``device_count`` as JAX reports
    them (so an artifact can never say "tpu" from a CPU run) plus HBM
    occupancy. Empty for a pure CPU-oracle run, which must not initialize
    the backend — and claim the exclusive chip out from under a concurrent
    device run — as a stats side effect.

    HBM sums over EVERY local device (the ISSUE 15 device-scope pass caught
    the old ``local_devices()[0]`` read): a sharded fleet's state lives
    spread across the mesh. The CPU test backend exposes no memory stats;
    on a TPU a missing or failing ``memory_stats()`` is an error the stats
    line shows (``hbm_error``), not an empty dict."""
    if not any(g.backend == "tpu" for g in groups):
        return {}
    import jax

    from rtap_tpu.utils.platform import device_info

    info = device_info()
    out = {"platform": info["platform"], "device_kind": info["kind"],
           "device_count": info["count"]}
    err = None
    try:
        per_device = [d.memory_stats() or {} for d in jax.local_devices()]
    except Exception as e:  # noqa: BLE001 — reported on the line, below
        per_device, err = [], repr(e)
    in_use = [s["bytes_in_use"] for s in per_device if "bytes_in_use" in s]
    if in_use:
        out["hbm_bytes_in_use"] = int(sum(in_use))
    peak = [s["peak_bytes_in_use"] for s in per_device
            if "peak_bytes_in_use" in s]
    if peak:
        out["hbm_peak_bytes_in_use"] = int(sum(peak))
    if info["platform"] == "tpu" and not in_use:
        out["hbm_error"] = err or "memory_stats() reported no bytes_in_use"
    return out
