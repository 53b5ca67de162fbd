"""Stream-group registry: many metric streams, few compiled programs.

The reference's stream manager lazily creates one NuPIC model per node-metric
stream and steps each in Python (SURVEY.md C19, §3.3). On TPU that shape is
wrong — thousands of tiny independent programs waste the chip. Here streams
are packed into fixed-capacity groups; all streams of a group share ONE
jitted vmapped step (ops/step.group_step), so a tick costs one device
dispatch per group and XLA compiles once per (config, group size).

`backend="cpu"` keeps the reference's default behavior (per-stream numpy
oracle models, no device) with the same API, preserving the plugin boundary:
CPU default, TPU opt-in per group (BASELINE.json north star).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from rtap_tpu.config import ModelConfig
from rtap_tpu.obs.trace import span
from rtap_tpu.service.likelihood_batch import BatchAnomalyLikelihood


@dataclass
class TickResult:
    """Scores for one tick of one group, index-aligned with group.stream_ids."""

    raw: np.ndarray  # [G] f32
    likelihood: np.ndarray  # [G] f64
    log_likelihood: np.ndarray  # [G] f64
    alerts: np.ndarray  # [G] bool
    prediction: np.ndarray | None = None  # [G] f32, when the classifier is on


PAD_PREFIX = "__pad"


def segment_capacity(in_use: np.ndarray) -> dict[str, int]:
    """How near the TM's dense segment pool is to its cap: `in_use` bool
    [..., C, K, S] (a slot holds a segment: ``seg_last >= 0``) ->

    - ``full_cells``: cells whose every ``max_segments_per_cell`` slot is in
      use — the next segment such a cell grows evicts its LRU one;
    - ``full_columns``: columns whose every cell is full. A burst allocates
      on the column's emptiest cell, so a segment is evicted for want of a
      slot only in such a column (segments also die, so a 0 read late says
      "not now", and with ``max_segments_on_a_cell`` far under the cap,
      "never");
    - ``max_segments_on_a_cell``: the pool's high-water mark.

    Pure host arithmetic on state a group already holds; never on the
    step's path."""
    per_cell = np.asarray(in_use).sum(-1)
    full = per_cell == np.shape(in_use)[-1]
    return {"full_cells": int(full.sum()),
            "full_columns": int(full.all(-1).sum()),
            "max_segments_on_a_cell": int(per_cell.max(initial=0))}


class StreamGroup:
    """G lockstep streams sharing one compiled device step (or one oracle loop).

    Slots whose id starts with ``__pad`` are capacity, not streams: they are
    fed NaN, never emitted, and can be CLAIMED mid-run by a new stream
    (:meth:`claim_slot` — the reference's lazy model creation, SURVEY.md C19)
    or returned by a departing one (:meth:`release_slot`). Claiming resets
    the slot's model state, likelihood moments + probation clock, and
    debounce counter, so a claimed slot is indistinguishable from a fresh
    model; the group's compiled program never changes (shapes are static —
    membership is data, not topology).

    ``health=True`` (ISSUE 6) makes every dispatched step additionally
    return the fused per-group model-health leaf (ops/health_tpu.py:
    occupancy/permanence/sparsity/hit-rate/score-histogram aggregates,
    ~200 B/tick); :meth:`collect_chunk` and :meth:`tick` stash it in
    ``self.last_health`` (numpy tree, leading tick axis) for the host
    HealthTracker to fold. Scores and model state are bit-identical with
    health on or off — the leaf is pure reads. Unsupported under a mesh
    (the aggregate would need a cross-shard collective, and
    sharded_chunk_step is collective-free by contract).

    ``predict=k`` > 0 (ISSUE 16) arms the predictive-horizon reducer
    (ops/predict_tpu.py) at horizon k: the state tree gains the
    predictor-owned ring/EWMA leaves and every dispatched step returns
    the per-stream divergence leaf, stashed in ``self.last_predict``
    exactly like health for the host PredictTracker (rtap_tpu/predict/)
    to fold. Model state and scores stay bit-identical with predict on
    or off (the model leaves are pure reads; the predictor leaves exist
    only when armed). Unsupported under a mesh for the same contract
    reason as health.

    ``make_state=False`` (tpu backend) builds the group with no state on the
    device: ``checkpoint.load_group`` hands it the saved one, so a load never
    holds a seeded state beside the one it restores.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        stream_ids: list[str],
        seed: int = 0,
        backend: str = "tpu",
        threshold: float = 0.5,
        mesh=None,
        debounce: int = 1,
        health: bool = False,
        predict: int = 0,
        make_state: bool = True,
    ):
        if debounce < 1:
            raise ValueError(f"debounce must be >= 1, got {debounce}")
        if health and mesh is not None:
            raise ValueError(
                "health reducers are unsupported on meshed groups: the "
                "per-group aggregate would need a cross-shard collective "
                "(sharded_chunk_step is collective-free by contract)")
        if predict < 0:
            raise ValueError(f"predict horizon must be >= 0, got {predict}")
        if predict and mesh is not None:
            raise ValueError(
                "the predictive-horizon reducer is unsupported on meshed "
                "groups (sharded_chunk_step is collective-free by "
                "contract, like health)")
        self.cfg = cfg
        self.stream_ids = list(stream_ids)
        self.G = len(self.stream_ids)
        self.seed = seed  # claim_slot re-inits a slot exactly as creation did
        self.backend = backend
        self.threshold = threshold
        # alert debouncing (SURVEY.md C20; round-4 quality study): a stream
        # alerts only after `debounce` CONSECUTIVE ticks at/above threshold.
        # False episodes are dominated by 1-2-tick likelihood flickers while
        # real faults persist (reports/quality_study.json), so debounce
        # trades a few ticks of latency for episode precision.
        self.debounce = int(debounce)
        self._alert_run = np.zeros(self.G, np.int64)  # consecutive hit count
        self.mesh = mesh
        self.health = bool(health)
        self.predict = int(predict)  # horizon k; 0 = predictor off
        # latest per-tick health leaves [T, ...] (health=True only);
        # kept in sync by collect_chunk and tick like last_predictions
        self.last_health: dict | None = None
        # latest per-tick predictive-horizon leaves [T, G] (predict > 0)
        self.last_predict: dict | None = None
        self.likelihood = BatchAnomalyLikelihood(cfg.likelihood, self.G)
        self.ticks = 0
        # alert-id timeline epoch: 0 for a group's original timeline;
        # bumped when a quarantine restore REWINDS self.ticks mid-run so
        # re-used tick indices never collide with already-delivered
        # alert_ids (docs/TELEMETRY.md alert schema; persisted in
        # checkpoint meta)
        self.alert_epoch = 0
        self._seq = 0  # dispatch sequence number (pipelined replay ordering)
        self._collected = 0
        # latest predicted values [T, G] (classifier only); kept in sync by
        # both run_chunk and tick so it can never serve stale data
        self.last_predictions: np.ndarray | None = None
        # conversions of the state between the public layout and the form
        # the device holds it in (ops/resident.py): set-up, a slot claimed,
        # a checkpoint, a row read — never a dispatched chunk or a live tick
        self.relayouts = 0
        if backend == "tpu" and not make_state:
            # checkpoint.load_group puts the saved state here: a state made
            # from the seed first would be a second group on the device
            self.resident = None
        elif backend == "tpu":
            from rtap_tpu.models.state import init_state
            from rtap_tpu.ops.resident import host_resident

            # the device holds the state in the form the kernel runs on, so
            # no program re-lays a pool on its way in or out; `self.state`
            # reads it as the public tree
            single = host_resident(
                init_state(cfg, seed, predict_horizon=self.predict), cfg.tm, self)
            if mesh is not None:
                # memory-lean: per-shard broadcast views, never the full
                # group on host (54 GiB at the 100k-stream scale)
                from rtap_tpu.parallel.sharding import broadcast_group_state

                self.resident = broadcast_group_state(single, self.G, mesh)
            else:
                # one ~0.5 MB transfer + on-chip broadcast, never a [G, ...]
                # host staging (208 s at the G=24k HBM frontier)
                from rtap_tpu.ops.step import replicate_state_device

                self.resident = replicate_state_device(single, self.G)
        else:
            from rtap_tpu.models.oracle.temporal_memory import TMOracle
            from rtap_tpu.models.state import init_state

            self._states = [
                init_state(cfg, seed, predict_horizon=self.predict)
                for _ in range(self.G)]
            self._tms = [TMOracle(s, cfg.tm) for s in self._states]
            self._classifiers = None
            if cfg.classifier.enabled:
                from rtap_tpu.models.oracle.classifier import SDRClassifierOracle

                self._classifiers = [
                    SDRClassifierOracle(s, cfg.classifier) for s in self._states
                ]

    # ---- the state, read from outside ----
    @property
    def state(self):
        """The group's model state as the public tree ([G, C, K, S, M]
        pools): a view of `resident`, what the device holds (tpu backend).
        Reading one stream's row slices the resident leaf first; assigning
        a tree or a leaf in the public layout converts it once, there
        (ops/resident.py)."""
        from rtap_tpu.ops.resident import PublicState

        return PublicState(self)

    @state.setter
    def state(self, tree) -> None:
        from rtap_tpu.ops.resident import resident_tree

        self.resident = resident_tree(tree, self.cfg.tm, self)

    # ---- dynamic membership (slots are static, streams are data) ----
    @property
    def n_live(self) -> int:
        return self.G - sum(
            1 for s in self.stream_ids if s.startswith(PAD_PREFIX))

    def live_slots(self) -> np.ndarray:
        """Slot indices holding real streams, ascending. For a group built
        without pads this is arange(G); emission and value routing index
        with it so pad/released slots never surface."""
        return np.array(
            [i for i, s in enumerate(self.stream_ids)
             if not s.startswith(PAD_PREFIX)], np.int64)

    def free_slot_count(self) -> int:
        return self.G - self.n_live

    # rtap: host-boundary — end-of-run stats fetch of seg_last ([G, C, K, S]
    # i32); off the step's path, like loop._overflow_total's
    def capacity_stats(self) -> dict[str, int]:
        """:func:`segment_capacity` of this group's streams, fetched from
        the state it holds (off the step's path, like ``tm_overflow``)."""
        if self.backend == "tpu":
            seg_last = np.asarray(self.state["seg_last"])  # the public [G, C, K, S]
        else:
            seg_last = np.stack([s["seg_last"] for s in self._states])
        return segment_capacity(seg_last >= 0)

    def claim_slot(self, stream_id: str) -> int:
        """Assign `stream_id` to a pad slot mid-run -> slot index.

        The slot's model state is re-initialized exactly as group creation
        initialized it (same config, same per-group seed), its likelihood
        moments and probation clock restart, and its debounce counter
        clears — a claimed slot behaves bit-for-bit like a stream that was
        registered into a fresh group (pinned by
        tests/unit/test_dynamic_streams.py). The compiled program is
        untouched: shapes are static, membership is data. Works on meshed
        groups too: the donated .at[slot].set lowers to a shard-local
        predicated update under GSPMD (the slot lives on exactly one
        shard), sharding preserved — tests/scale/test_sharded.py pins
        bit-exactness vs the single-device claim.
        """
        if stream_id.startswith(PAD_PREFIX):
            raise ValueError(f"stream id may not start with {PAD_PREFIX!r}")
        if stream_id in self.stream_ids:
            raise KeyError(f"duplicate stream id {stream_id!r}")
        slot = next((i for i, s in enumerate(self.stream_ids)
                     if s.startswith(PAD_PREFIX)), None)
        if slot is None:
            raise RuntimeError(
                f"group is full ({self.G} live streams); capacity comes "
                "from pad slots (group-size rounding or released streams)")
        self._reset_slot_state(slot)
        self.stream_ids[slot] = stream_id
        return slot

    def release_slot(self, stream_id: str) -> int:
        """Return a stream's slot to pad capacity -> freed slot index.

        The slot stops being fed and emitted immediately; its state stays
        in place (harmlessly ticking on NaN) until a future claim resets
        it. The id becomes available for re-registration elsewhere."""
        try:
            slot = self.stream_ids.index(stream_id)
        except ValueError:
            raise KeyError(f"unknown stream id {stream_id!r}") from None
        # unique pad name: a plain __pad<i> could collide with creation pads
        self.stream_ids[slot] = f"{PAD_PREFIX}!released{slot}"
        self._alert_run[slot] = 0
        return slot

    def _reset_slot_state(self, slot: int) -> None:
        from rtap_tpu.models.state import init_state

        fresh = init_state(self.cfg, self.seed, predict_horizon=self.predict)
        if self.predict:
            # the claimed slot's predictor warm-up restarts NOW: its ring
            # is zeroed, and scoring a real tick against a zeroed ring
            # would fake a full-divergence precursor (ops/predict_tpu.py
            # gates scoring on tick >= pred_tick0 + horizon)
            fresh["pred_tick0"] = np.int32(self.ticks)
        if self.backend == "tpu":
            from rtap_tpu.ops.resident import host_resident
            from rtap_tpu.ops.step import set_state_row

            # match the live tree's structure (forward-index mode carries
            # derived fwd_* leaves that init_state also builds) and its
            # form: the fresh row is re-laid on the host, one row's worth
            fresh = host_resident(
                {k: fresh[k] for k in self.resident}, self.cfg.tm, self)
            self.resident = set_state_row(self.resident, fresh, slot)
        else:
            from rtap_tpu.models.oracle.temporal_memory import TMOracle

            self._states[slot] = fresh
            self._tms[slot] = TMOracle(fresh, self.cfg.tm)
            if self._classifiers is not None:
                from rtap_tpu.models.oracle.classifier import SDRClassifierOracle

                self._classifiers[slot] = SDRClassifierOracle(
                    fresh, self.cfg.classifier)
        self.likelihood.reset_slot(slot)
        self._alert_run[slot] = 0

    def _raw_cpu(self, values: np.ndarray, ts: np.ndarray, learn: bool = True):
        from rtap_tpu.models.htm_model import oracle_record_step

        if learn and self.cfg.cadence_active:
            # host twin of the device schedule (ops/step.py:_tick): same
            # clock (tm_iter = completed steps, lockstep across the group),
            # same predicate (cfg.learns_on) — without this the CPU backend
            # would silently ignore the learning cadence and backends would
            # diverge (caught by the r4 cadence quality sweep coming back
            # bit-identical across k)
            learn = bool(self.cfg.learns_on(int(self._states[0]["tm_iter"])))
        raw = np.empty(self.G, np.float32)
        pred = np.empty(self.G, np.float32) if self._classifiers else None
        for g in range(self.G):
            out = oracle_record_step(
                self.cfg, self._states[g], self._tms[g], values[g], int(ts[g]), learn,
                classifier=self._classifiers[g] if self._classifiers else None,
            )
            if self._classifiers:
                raw[g], pred[g] = out[0], out[1]
            else:
                raw[g] = out
        return raw, pred

    def _put(self, x: np.ndarray, axis: int = 0):
        """Host array -> device, sharded on the stream axis when meshed.

        For chunked arrays [T, G, ...] the stream axis is 1; sharding is
        expressed on that axis (the leading time axis is replicated)."""
        import jax
        import jax.numpy as jnp

        if self.mesh is None:
            return jnp.asarray(x)
        from rtap_tpu.parallel.sharding import put_sharded

        return put_sharded(np.asarray(x), self.mesh, axis)

    def tick(self, values: np.ndarray, ts: np.ndarray | int, learn: bool = True) -> TickResult:
        """Score one tick. `values` [G] or [G, n_fields]; `ts` scalar or [G]."""
        values = np.asarray(values, np.float32)
        if values.ndim == 1:
            values = values[:, None]
        ts = np.broadcast_to(np.asarray(ts, np.int32), (self.G,))
        pred = None
        if self.backend == "tpu":
            if self.mesh is not None:
                from rtap_tpu.ops.step import sharded_chunk_step

                self.resident, out = sharded_chunk_step(
                    self.resident, self._put(values[None], axis=1),
                    self._put(ts[None].astype(np.int32), axis=1), self.cfg, self.mesh,
                    learn=learn,
                )
                raw, pred = self._unpack_out(out, time_axis=True)
            else:
                from rtap_tpu.ops.step import group_step

                self.resident, out = group_step(
                    self.resident, self._put(values), self._put(ts.astype(np.int32)), self.cfg,
                    learn=learn, health=self.health,
                    predict=bool(self.predict),
                )
                if self.predict:  # wraps outermost (ops/step.py _tick)
                    out, pleaf = out
                    self.last_predict = {
                        k: np.asarray(v)[None, ...] for k, v in pleaf.items()}
                if self.health:
                    out, health = out
                    self.last_health = {
                        k: np.asarray(v)[None, ...] for k, v in health.items()}
                raw, pred = self._unpack_out(out, time_axis=False)
        else:
            raw, pred = self._raw_cpu(values, ts, learn)
            if self.health:
                from rtap_tpu.ops.health_tpu import health_from_states

                self.last_health = {
                    k: np.asarray(v)[None, ...] for k, v in
                    health_from_states(self._states, raw, values,
                                       self.cfg).items()}
            if self.predict:
                from rtap_tpu.models.oracle.predict import predict_from_states

                self.last_predict = {
                    k: np.asarray(v)[None, ...] for k, v in
                    predict_from_states(self._states, values,
                                        self.cfg).items()}
        self.last_predictions = None if pred is None else pred[None, :]
        self.ticks += 1
        lik, loglik = self.likelihood.update(raw)
        return TickResult(raw, lik, loglik, self._debounced(loglik), pred)

    def _debounced(self, loglik: np.ndarray) -> np.ndarray:
        """Advance the consecutive-hit counters one tick -> alert mask [G]."""
        hits = loglik >= self.threshold
        self._alert_run = np.where(hits, self._alert_run + 1, 0)
        return self._alert_run >= self.debounce

    def _unpack_out(self, out, time_axis: bool):
        """Device step output -> (raw [G], pred [G]|None); strips the leading
        1-tick time axis of the sharded path when present."""
        if self.cfg.classifier.enabled:
            raw, pred = np.asarray(out[0]), np.asarray(out[1])
        else:
            raw, pred = np.asarray(out), None
        if time_axis:
            raw = raw[0]
            pred = None if pred is None else pred[0]
        return raw, pred

    def dispatch_chunk(self, values: np.ndarray, ts: np.ndarray, learn: bool = True) -> dict:
        """Enqueue T ticks on the device WITHOUT blocking on the result.

        JAX dispatch is asynchronous: this returns as soon as the transfer +
        step program are queued, so the host can overlap the previous chunk's
        likelihood post-process (and the next chunk's staging) with device
        compute — the double-buffered feed of SURVEY.md §7 hard part 3.
        Returns an opaque handle for :meth:`collect_chunk`. Handles MUST be
        collected in dispatch order (the likelihood ring is sequential).

        On the CPU backend there is no async device; the chunk is computed
        here and the handle carries the finished scores.
        """
        # the chunk path's four phases are host spans of the served path's one
        # seam (obs/trace.py:SPANS): `rtap.group.stage` (host arrays ->
        # device), `.enqueue` (the step program up to the handle), `.fetch`
        # (the blocking device -> host read: in a live tick mostly the wait
        # behind the other groups' programs), `.likelihood` (host likelihood
        # + debounce). `group` (the first stream id) and `seq` (the handle's)
        # tie one chunk's four spans to each other, to the loop's per-group
        # spans and to the chunk's execution on the device.
        gid = self.stream_ids[0]
        seq = self._seq + 1  # the handle's, so one chunk's phases share it
        with span("rtap.group.stage", group=gid, seq=seq):
            values = np.asarray(values, np.float32)
            if values.ndim == 2:
                values = values[..., None]
            T = values.shape[0]
            if self.backend == "tpu":
                dev_values = self._put(values, axis=1)
                dev_ts = self._put(ts.astype(np.int32), axis=1)
        if self.backend == "tpu":
            with span("rtap.group.enqueue", group=gid, seq=seq):
                if self.mesh is not None:
                    from rtap_tpu.ops.step import sharded_chunk_step

                    self.resident, out = sharded_chunk_step(
                        self.resident, dev_values, dev_ts, self.cfg, self.mesh,
                        learn=learn,
                    )
                else:
                    from rtap_tpu.ops.step import chunk_step

                    self.resident, out = chunk_step(
                        self.resident, dev_values, dev_ts,
                        self.cfg, learn=learn, health=self.health,
                        predict=bool(self.predict),
                    )
                health = None
                predict = None
                if self.predict and self.mesh is None:
                    # predict wraps outermost (ops/step.py _tick)
                    out, predict = out
                if self.health and self.mesh is None:
                    out, health = out
                # seq advances only on successful dispatch: a raise above must
                # leave the pipeline collectable, not permanently desynced
                self._seq = seq
                return {"out": out, "health": health, "predict": predict,
                        "T": T, "seq": seq, "device": True}
        outs = []
        hticks = []
        pticks = []
        for i in range(T):
            o = self._raw_cpu(values[i], np.asarray(ts[i]), learn)
            outs.append(o)
            if self.health:
                # host twin of the fused reducer, on the post-tick oracle
                # states (same schema as the device leaf, [T, ...] stacked)
                from rtap_tpu.ops.health_tpu import health_from_states

                hticks.append(health_from_states(
                    self._states, o[0], values[i], self.cfg))
            if self.predict:
                from rtap_tpu.models.oracle.predict import predict_from_states

                pticks.append(predict_from_states(
                    self._states, values[i], self.cfg))
        raw = np.stack([o[0] for o in outs])
        pred = np.stack([o[1] for o in outs]) if self.cfg.classifier.enabled else None
        health = {k: np.stack([h[k] for h in hticks]) for k in hticks[0]} \
            if hticks else None
        predict = {k: np.stack([p[k] for p in pticks]) for k in pticks[0]} \
            if pticks else None
        self._seq += 1
        return {"raw": raw, "pred": pred, "health": health,
                "predict": predict, "T": T, "seq": self._seq,
                "device": False}

    # rtap: host-boundary — collect_chunk IS the chunk's blocking device ->
    # host fetch (span rtap.group.fetch): the scores and, where armed, the
    # reducers' small leaves in one read; health and predict are refused
    # under a mesh, so that read gathers no shard
    def collect_chunk(self, handle: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Block on a dispatched chunk -> (raw [T,G], log_likelihood [T,G],
        alerts [T,G]); classifier predictions land in `self.last_predictions`."""
        if handle["seq"] != self._collected + 1:
            raise RuntimeError(
                f"collect_chunk out of order: handle seq {handle['seq']}, "
                f"expected {self._collected + 1} (likelihood state is sequential)"
            )
        gid, seq = self.stream_ids[0], handle["seq"]
        with span("rtap.group.fetch", group=gid, seq=seq):
            if handle["device"]:
                # the blocking fetch can surface a device error — only a chunk
                # whose scores actually materialized counts as collected
                raw, pred = self._unpack_out(handle["out"], time_axis=False)
            else:
                raw, pred = handle["raw"], handle["pred"]
            leaves = {k: handle[k] for k in ("health", "predict")
                      if handle.get(k) is not None}
            if leaves and handle["device"]:
                # the reducers' leaves ride the same blocking boundary as
                # the scores, in ONE device -> host read: eleven health
                # leaves (~200 B a tick) and four predict leaves (13 B a
                # stream-tick, predict_nbytes) read one `np.asarray` at a
                # time were fifteen round trips a group-tick, 6.5 ms of a
                # node fleet's tick after its last program (PERF.md §6)
                import jax

                leaves = jax.device_get(leaves)
            if "health" in leaves:
                self.last_health = {
                    k: np.asarray(v) for k, v in leaves["health"].items()}
            if "predict" in leaves:
                self.last_predict = {
                    k: np.asarray(v) for k, v in leaves["predict"].items()}
        self._collected = handle["seq"]
        T = handle["T"]
        self.last_predictions = pred
        self.ticks += T
        with span("rtap.group.likelihood", group=gid, seq=seq):
            loglik = np.empty((T, self.G))
            alerts = np.empty((T, self.G), bool)
            for i in range(T):
                _, loglik[i] = self.likelihood.update(raw[i])
                alerts[i] = self._debounced(loglik[i])
        return raw, loglik, alerts

    def run_chunk(self, values: np.ndarray, ts: np.ndarray, learn: bool = True) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Replay T ticks in one device dispatch, synchronously.

        `values` [T, G] or [T, G, n_fields], `ts` [T, G] ->
        (raw [T, G], log_likelihood [T, G], alerts [T, G]). When the SDR
        classifier is enabled, per-tick predicted values land in
        `self.last_predictions` [T, G]. For the overlapped replay fast path
        use :meth:`dispatch_chunk` + :meth:`collect_chunk` instead.
        """
        return self.collect_chunk(self.dispatch_chunk(values, ts, learn))


@dataclass(frozen=True)
class SlotAddress:
    """A stream's (shard, group, slot) address — the pod-scale
    addressing the source layer routes by (ROADMAP-1; ISSUE 7).

    ``shard`` is the device-mesh shard that owns the slot's state row
    (0 everywhere on a single device; under a mesh the stream axis is
    block-sharded, so shard = slot * n_shards // G). The binary ingest
    protocol packs this triple into its wire slot code
    (rtap_tpu/ingest/protocol.encode_slot)."""

    shard: int
    group: int
    slot: int


@dataclass
class _Slot:
    group: StreamGroup
    index: int


class StreamGroupRegistry:
    """Lazy stream_id -> (group, slot) assignment, the C19 analog.

    Streams are assigned to the open group until it reaches `group_size`,
    then a new group opens. All groups share one ModelConfig so XLA compiles
    the step once per group size (sizes are padded to `group_size` at
    creation; short groups waste slots, not compilations).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        group_size: int = 1024,
        backend: str = "tpu",
        seed: int = 0,
        threshold: float = 0.5,
        mesh=None,
        debounce: int = 1,
        stagger_learn: bool = False,
        health: bool = False,
        predict: int = 0,
    ):
        self.cfg = cfg
        self.health = bool(health)
        self.predict = int(predict)
        # Stagger the learning-cadence phase across groups (group i learns
        # on ticks where (it - i % learn_every) % learn_every == 0): with
        # every group at phase 0 the whole fleet learns on the SAME ticks,
        # so per-tick device compute spikes to the full-fleet learning cost
        # and idles in between — at 100k streams the spike alone exceeds
        # the 1 s cadence that the AVERAGE load fits comfortably.
        # Per-group semantics are identical up to a <k-tick schedule shift;
        # phases derive deterministically from the group index, so a
        # resumed registry rebuilt with the same flags reproduces them.
        self.stagger_learn = bool(stagger_learn) and cfg.learn_every > 1
        self.group_size = int(group_size)
        self.backend = backend
        self.seed = seed
        self.threshold = threshold
        self.debounce = int(debounce)
        self.mesh = mesh
        self.groups: list[StreamGroup] = []
        self._slots: dict[str, _Slot] = {}
        self._pending: list[str] = []
        self._finalized = False
        # bumped on every post-finalize membership change; live_loop watches
        # it to rebuild value/emission routing without re-deriving per tick
        self.version = 0

    def add_stream(self, stream_id: str) -> None:
        """Register a stream. Before :meth:`finalize`: buffered into the
        next group (the bulk path). After: the stream CLAIMS a free pad
        slot in the first group with capacity — the reference's lazy
        model-per-stream creation (SURVEY.md C19), with no recompile
        (shapes are static). Raises RuntimeError when every slot is live;
        capacity comes from group-size rounding, `reserve` slots, or
        released streams."""
        if stream_id.startswith(PAD_PREFIX):
            # same guard claim_slot enforces: a pad-prefixed id on the bulk
            # path would silently read as pad capacity (never emitted, its
            # slot re-claimable) — two index entries, one slot
            raise ValueError(f"stream id may not start with {PAD_PREFIX!r}")
        if stream_id in self._slots or stream_id in self._pending:
            raise KeyError(f"duplicate stream id {stream_id!r}")
        if self._finalized:
            for grp in self.groups:
                if grp.free_slot_count():
                    slot = grp.claim_slot(stream_id)
                    self._slots[stream_id] = _Slot(grp, slot)
                    self.version += 1
                    return
            raise RuntimeError(
                f"registry at capacity ({len(self._slots)} live streams, 0 "
                "free slots): pre-provision with reserve= or release "
                "departed streams")
        self._pending.append(stream_id)
        if len(self._pending) == self.group_size:
            self._seal()

    def remove_stream(self, stream_id: str) -> None:
        """Release a departed stream's slot back to pad capacity: it stops
        being fed and emitted next tick, and the slot becomes claimable by
        a future add_stream (which resets its state). Post-finalize only —
        before finalize just don't add it."""
        if not self._finalized:
            raise RuntimeError("remove_stream is a post-finalize operation")
        s = self._slots.pop(stream_id, None)
        if s is None:
            raise KeyError(f"unknown stream id {stream_id!r}")
        s.group.release_slot(stream_id)
        self.version += 1

    def _seal(self) -> None:
        if not self._pending:
            return
        ids = self._pending
        # pad to the fixed group size so every group compiles to one program
        padded = ids + [f"__pad{i}" for i in range(self.group_size - len(ids))]
        grp = StreamGroup(
            self._group_cfg(len(self.groups)), padded,
            seed=self.seed + len(self.groups),
            backend=self.backend, threshold=self.threshold, mesh=self.mesh,
            debounce=self.debounce, health=self.health, predict=self.predict,
        )
        for i, sid in enumerate(ids):
            self._slots[sid] = _Slot(grp, i)
        self.groups.append(grp)
        self._pending = []

    def finalize(self, reserve: int = 0) -> None:
        """Seal the last partially-filled group (call once ingestion is
        known). `reserve` adds that many extra pad slots of claimable
        capacity for post-finalize registration (rounded up to whole
        groups of `group_size`; each reserve group is all-pad until
        streams claim into it)."""
        if reserve < 0:
            raise ValueError(f"reserve must be >= 0; got {reserve}")
        # account pads the natural rounding already leaves in the last group
        rounding_pads = (-len(self._pending)) % self.group_size \
            if self._pending else 0
        self._seal()
        extra = max(0, reserve - rounding_pads)
        for _ in range((extra + self.group_size - 1) // self.group_size):
            self._seal_all_pad()
        self._finalized = True

    def _group_cfg(self, gi: int) -> ModelConfig:
        """The config group `gi` is built with: the registry cfg, cadence
        phase-shifted by gi when stagger_learn is on (at most learn_every
        distinct compiled programs fleet-wide — the phase is a static
        config field). With learn_burst=B the schedule's cycle is k*B
        ticks and a useful stagger offsets whole B-tick bursts: phase
        (gi mod k) * B puts exactly 1/k of the groups in their burst on
        any post-maturity tick — the same leveling the spread schedule
        gets from gi mod k."""
        if not self.stagger_learn:
            return self.cfg
        import dataclasses

        return dataclasses.replace(
            self.cfg,
            learn_phase=(gi % self.cfg.learn_every) * self.cfg.learn_burst)

    def _seal_all_pad(self) -> None:
        """Append one all-pad reserve group (claimable capacity)."""
        grp = StreamGroup(
            self._group_cfg(len(self.groups)),
            [f"{PAD_PREFIX}{i}" for i in range(self.group_size)],
            seed=self.seed + len(self.groups), backend=self.backend,
            threshold=self.threshold, mesh=self.mesh, debounce=self.debounce,
            health=self.health, predict=self.predict,
        )
        self.groups.append(grp)

    def lookup(self, stream_id: str) -> tuple[StreamGroup, int]:
        s = self._slots[stream_id]
        return s.group, s.index

    def __contains__(self, stream_id: str) -> bool:
        return stream_id in self._slots or stream_id in self._pending

    def dispatch_ids(self) -> list[str]:
        """Live stream ids in (group, slot) order — the value-vector order
        live_loop's routing and every source snapshot must follow."""
        return [g.stream_ids[i] for g in self.groups for i in g.live_slots()]

    def slot_map(self) -> dict[str, SlotAddress]:
        """Live stream id -> (shard, group, slot) address — what the
        registry hands sources instead of a flat id list (ROADMAP-1).

        Iterating the map in (group, slot) order reproduces
        :meth:`dispatch_ids` exactly (pinned by
        tests/unit/test_ingest_protocol.py), so a source that scatters
        by address and a loop that routes positionally agree by
        construction. Pads/released slots are absent — a wire record
        addressed at one is an unknown, not a write."""
        out: dict[str, SlotAddress] = {}
        for gi, g in enumerate(self.groups):
            n_shards = 1
            if g.mesh is not None:
                n_shards = int(g.mesh.devices.size)
                from rtap_tpu.ingest.protocol import MAX_SHARDS

                if n_shards > MAX_SHARDS:
                    raise ValueError(
                        f"mesh has {n_shards} devices but the ingest "
                        f"slot code carries {MAX_SHARDS} shards max "
                        "(rtap_tpu/ingest/protocol.py SHARD_BITS; a "
                        "wider mesh needs a protocol magic bump)")
            for slot in g.live_slots():
                slot = int(slot)
                out[g.stream_ids[slot]] = SlotAddress(
                    shard=slot * n_shards // g.G, group=gi, slot=slot)
        return out

    @property
    def free_slots(self) -> int:
        return sum(g.free_slot_count() for g in self.groups)

    @property
    def n_streams(self) -> int:
        return len(self._slots) + len(self._pending)
