"""Fused per-record device step: encode -> SP -> TM -> raw anomaly score.

This is the TPU-native analog of the reference's per-record hot path
(SURVEY.md §3.2: `model.run` -> encoders -> SpatialPooler.cpp ->
Cells4/TemporalMemory.cpp -> raw score), collapsed into ONE jitted XLA
program so a record costs a single device dispatch. The host boundary is
exactly the one BASELINE.json prescribes: values/timestamps in, raw scores
out; anomaly likelihood stays on host (models/oracle/likelihood.py,
service/likelihood_batch.py).

Entry points:

- :func:`fused_step` — single stream, used by `HTMModel(backend="tpu")`.
- :func:`group_step` — vmapped over a leading stream-group axis G: one
  dispatch scores G streams in lockstep (SURVEY.md §2.3 "DP over streams").
- :func:`chunk_step` — `group_step`'s body scanned over T ticks: the program
  the stream groups call, in replay (T = 8) and in the live loop (T = 1).
- :class:`TpuStepRunner` — stateful convenience wrapper holding device state.

All are bit-identical to the CPU oracle per step
(tests/parity/test_e2e_parity.py). A program runs the kernel's form; a
public [C, K, S, M] tree (the harnesses that build their own) is converted
once each way at its boundary (:func:`_enter_kernel`) and handed back
public, and the owners that keep theirs on the device in the kernel's form
between programs (ops/resident.py) pay no layout change at all.
"""

from __future__ import annotations

import functools as _functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from rtap_tpu.config import ModelConfig
from rtap_tpu.ops.encoders_tpu import bind_offsets, encode_device
from rtap_tpu.ops.sp_tpu import sp_step
from rtap_tpu.ops.tm_tpu import tm_step

#: The names the step writes into the profiler's trace: every stage of a
#: tick runs under `jax.named_scope(<name>)`, which lands in the `op_name`
#: metadata of each XLA op it produces (and nowhere else: no operand, fusion
#: decision or output changes). This tuple is the one place that lists the
#: vocabulary; a reader of a device trace matches the prefix `rtap.` in an
#: op's name, innermost match first (under vmap/scan/cond JAX wraps entries:
#: `vmap(rtap.sp.overlap)`, `while/body/...`).
#:
#:   rtap.encode         bind_offsets + encode_device (+ enc_prev)  (_step_impl)
#:   rtap.sp.overlap     sp_overlap incl. the member bit test       (sp_tpu.sp_step)
#:   rtap.sp.inhibit     sp_inhibit                                 (sp_tpu.sp_step)
#:   rtap.sp.learn       sp_learn                                   (sp_tpu.sp_step)
#:   rtap.tm.activate    cell activation, winners, raw score        (tm_tpu.tm_step)
#:   rtap.tm.learn       reinforce/punish/grow                      (tm_tpu.tm_step)
#:   rtap.tm.learn.rows  the workspace's rows moved by index: the form
#:                       wide pool rows take (tm_tpu.wide_rows); absent
#:                       where one-hot moves carry them               (tm_tpu.tm_step)
#:   rtap.tm.dendrite    dendrite activity for t+1                  (tm_tpu.tm_step)
#:   rtap.reduce.health, rtap.reduce.predict, rtap.classifier
#:                       the optional reducers / classifier         (_tick, _step_impl)
#:   rtap.layout         resident_form / public_form of a PUBLIC-layout
#:                       argument, once per program each way; absent where
#:                       the state arrives in the kernel's form (a stream
#:                       group's)                                   (_enter_kernel)
SCOPES = (
    "rtap.encode",
    "rtap.sp.overlap", "rtap.sp.inhibit", "rtap.sp.learn",
    "rtap.tm.activate", "rtap.tm.learn", "rtap.tm.learn.rows",
    "rtap.tm.dendrite",
    "rtap.reduce.health", "rtap.reduce.predict", "rtap.classifier",
    "rtap.layout",
)


def _step_impl(state: dict, values: jnp.ndarray, ts_unix: jnp.ndarray, cfg: ModelConfig, learn: bool,
              inv: dict | None = None):
    """One fused record step -> (new_state, out). Pure/traceable.

    `values` is [n_fields] f32 (NaN = missing sample), `ts_unix` scalar i32.
    `out` is the raw anomaly score (f32 scalar), or the tuple
    (raw, predicted_value, prediction_prob) when the SDR classifier is
    enabled (cfg.classifier.enabled — a static property, so call sites can
    unpack unconditionally for a given config). `inv` carries tm_step's
    tick-invariant operands (ops/tm_tpu.tm_invariants) when the caller
    hoists them out of a scan; None rebuilds them in-trace.
    """
    with jax.named_scope("rtap.encode"):
        enc_offset, enc_bound = bind_offsets(values, state["enc_offset"], state["enc_bound"])
        state = {**state, "enc_offset": enc_offset, "enc_bound": enc_bound}
        enc_prev = state.get("enc_prev")  # composite delta fields only
        sdr = encode_device(cfg, values, ts_unix, enc_offset,
                            state["enc_resolution"], enc_prev)
        if enc_prev is not None:
            # the delta predecessor advances to the last FINITE value AFTER
            # encoding (this tick encoded against the pre-tick predecessor);
            # NaN gaps keep the pre-gap baseline, mirroring offset binding
            state["enc_prev"] = jnp.where(jnp.isfinite(values), values, enc_prev)
    pattern_prev = state["prev_active"]  # TM active cells at t-1
    state, active = sp_step(state, sdr, cfg.sp, learn)
    state, raw = tm_step(state, active, cfg.tm, learn, inv=inv)
    if cfg.classifier.enabled:
        from rtap_tpu.ops.classifier_tpu import classifier_step

        with jax.named_scope("rtap.classifier"):
            state, pred, conf = classifier_step(
                state, pattern_prev, state["prev_active"], values[0], cfg, learn
            )
        return state, (raw, pred, conf)
    return state, raw


def _enter_kernel(state: dict, cfg: ModelConfig):
    """A program's state argument -> (the tree `tm_step` runs on, the
    function that hands the stepped tree back in the form the argument came
    in). A program runs the kernel's form; which form it was handed is told
    by the leaves' shapes at trace time (tm_tpu.kernel_resident), never by a
    setting or by the program's length:

    - the kernel's form (what a `StreamGroup` or a `TpuStepRunner` holds on
      the device between programs, ops/resident.py) passes both ways
      untouched — the program's parameters and results ARE the scan's
      carry, and no pool is copied at its boundary;
    - the public [C, K, S, M] layout (the parity harness, the oracle's
      twin, scripts that build their own trees) is converted once each way
      at the program's boundary, `resident_form` in and `public_form` out,
      under `rtap.layout`.
    """
    from rtap_tpu.ops.tm_tpu import kernel_resident, public_form, resident_form

    if kernel_resident(state):
        return state, lambda stepped: stepped
    with jax.named_scope("rtap.layout"):
        entered = resident_form(state, cfg.tm)

    def leave(stepped: dict) -> dict:
        with jax.named_scope("rtap.layout"):
            return public_form(stepped, cfg.tm)

    return entered, leave


# rtap: twin[oracle_record_step] — the oracle chains bind/encode/SP/TM
# per record (models/htm_model.py); parity: tests/parity/test_e2e_parity.py
@partial(jax.jit, static_argnames=("cfg", "learn"))
def fused_step(state: dict, values: jnp.ndarray, ts_unix: jnp.ndarray, cfg: ModelConfig, learn: bool = True):
    """Single-stream fused step (see :func:`_step_impl`). The state comes
    back in the form it arrived in (:func:`_enter_kernel`)."""
    state, leave = _enter_kernel(state, cfg)
    state, out = _step_impl(state, values, ts_unix, cfg, learn)
    return leave(state), out


def _tick(s: dict, values: jnp.ndarray, ts_unix: jnp.ndarray, cfg: ModelConfig, learn: bool,
          inv: dict | None = None, health: bool = False, predict: bool = False):
    """One group tick on KERNEL-layout state, honoring cfg.learn_every.

    With a learning cadence (cfg.learn_every > 1 and learn=True) the
    learn/infer choice is a `lax.cond` on a SCALAR schedule flag derived
    from the group's lockstep tick counter (`tm_iter`, which advances under
    inference too) — the cond must sit OUTSIDE the vmap: a per-stream
    predicate would lower to select and execute BOTH branches, paying the
    learning pass it exists to skip. Groups tick in lockstep (registry
    invariant), so one flag serves all G streams.

    `inv` (tm_invariants) is closed over, NOT vmapped: one shared
    HBM-resident copy serves all G streams.

    `health=True` (static) additionally reduces the POST-STEP state to
    one small per-group health leaf (ops/health_tpu.py) and returns
    (state, (out, health_leaf)). Pure reads on the tensors the step just
    produced — the model state and scores are bit-identical either way
    (tests/integration/test_health_serve.py pins it), and the leaf adds
    ~200 bytes to the chunk output instead of a device->host state fetch.

    `predict=True` (static, ISSUE 16) additionally folds the predictive-
    horizon reducer (ops/predict_tpu.py) — it updates ONLY the
    predictor-owned ring/EWMA leaves and wraps the per-stream leaf
    OUTERMOST: (state, (inner, predict_leaf)) where `inner` is whatever
    the health flag produced, so existing unpack sites are untouched.
    Requires the predictor leaves in the state tree (the registry builds
    them via init_state(predict_horizon=...)).
    """

    def step_all(lrn):
        return lambda ss: jax.vmap(
            lambda s1, vv, tt: _step_impl(s1, vv, tt, cfg, lrn, inv)
        )(ss, values, ts_unix)

    if not (learn and cfg.cadence_active):
        s, out = step_all(learn)(s)
    else:
        tick = s["tm_iter"].reshape(-1)[0]  # completed steps so far (lockstep)
        s, out = jax.lax.cond(
            cfg.learns_on(tick), step_all(True), step_all(False), s)
    if predict:
        from rtap_tpu.ops.predict_tpu import predict_update

        with jax.named_scope("rtap.reduce.predict"):
            s, pleaf = predict_update(s, values, cfg)
    if health:
        from rtap_tpu.ops.health_tpu import health_reduce

        raw = out[0] if cfg.classifier.enabled else out
        with jax.named_scope("rtap.reduce.health"):
            out = (out, health_reduce(s, raw, values, cfg))
    if predict:
        out = (out, pleaf)
    return s, out


# rtap: twin[oracle_record_step] — vmapped form of the same oracle chain
@partial(jax.jit, static_argnames=("cfg", "learn", "health", "predict"), donate_argnums=(0,))
def group_step(state: dict, values: jnp.ndarray, ts_unix: jnp.ndarray, cfg: ModelConfig, learn: bool = True,
               health: bool = False, predict: bool = False):
    """Stream-group fused step: every state leaf carries a leading G axis;
    `values` is [G, n_fields] f32, `ts_unix` [G] i32 -> (state, raw [G] f32).

    State buffers are donated: at 100k streams the TM pools dominate HBM and
    the update must happen in place (SURVEY.md §7 hard part 4).
    With `health=True` the out leaf becomes (out, health_leaf); with
    `predict=True` the predictive-horizon leaf wraps outermost — see
    :func:`_tick` / ops/health_tpu.py / ops/predict_tpu.py.
    """
    state, leave = _enter_kernel(state, cfg)
    state, out = _tick(state, values, ts_unix, cfg, learn,
                       health=health, predict=predict)
    return leave(state), out


def _scan_chunk(state: dict, values: jnp.ndarray, ts_unix: jnp.ndarray, cfg: ModelConfig, learn: bool,
                health: bool = False, predict: bool = False):
    """Shared hot-loop body: scan the vmapped fused step over the time axis.
    Used identically by the single-device and shard_map entry points, so the
    two can never diverge semantically.

    The carry holds the pools in the kernel's layout for all T ticks
    (tm_tpu.wide_rows: flat at narrow pool rows, [C, M, K*S] at wide ones).
    A stream group hands its state over in that form and takes it back so
    (ops/resident.py): the program's parameters are the carry. A tree in
    the public [C,K,S,M] layout — the parity harness's, the oracle twin's —
    is converted OUTSIDE the scan, once per chunk each way (a reshape at
    narrow rows, a transpose a pool at wide ones); checkpoints and the
    oracle never see the kernel layout, and readers of a group's state see
    it through `StreamGroup.state`, public again. Likewise the
    tick-invariant kernel operands (the flat layout's per-segment reduction
    matrix) are built ONCE here and closed over by the body, so they are
    hoisted out of the scan by construction and stay HBM-resident across
    the whole T-tick chunk."""
    from rtap_tpu.ops.tm_tpu import tm_invariants

    inv = tm_invariants(cfg.tm)

    def body(s, inp):
        v, t = inp
        return _tick(s, v, t, cfg, learn, inv, health=health,
                     predict=predict)

    state, leave = _enter_kernel(state, cfg)
    state, out = jax.lax.scan(body, state, (values, ts_unix))
    return leave(state), out


# rtap: twin[oracle_record_step] — time-scanned form of the oracle chain
@partial(jax.jit, static_argnames=("cfg", "learn", "health", "predict"), donate_argnums=(0,))
def chunk_step(state: dict, values: jnp.ndarray, ts_unix: jnp.ndarray, cfg: ModelConfig, learn: bool = True,
               health: bool = False, predict: bool = False):
    """Multi-tick stream-group step: scan :func:`group_step`'s body over a
    leading time axis so T ticks cost ONE device dispatch.

    `values` is [T, G, n_fields] f32, `ts_unix` [T, G] i32 ->
    (state, raw [T, G] f32). This is the replay/bench fast path (SURVEY.md §7
    hard part 3: amortize per-tick dispatch latency by batching ticks when
    replaying faster than real time) and, at T = 1 (or the micro-chunk's
    length), the live service's too: `StreamGroup.dispatch_chunk` is what
    `live_loop` calls, and :func:`group_step` serves `StreamGroup.tick`
    alone. With `health=True` (static) the
    out leaf becomes (out, health_leaf) and every health-leaf array gains
    the leading T axis — one ~200 B record per tick, scanned alongside the
    scores (ops/health_tpu.py). With `predict=True` the predictive-horizon
    leaf rides the same way, wrapped outermost ([T, G] per-stream vectors
    beside the scores — ops/predict_tpu.py).
    """
    return _scan_chunk(state, values, ts_unix, cfg, learn, health=health,
                       predict=predict)


@_functools.lru_cache(maxsize=None)
def _sharded_chunk_fn(cfg: ModelConfig, mesh, learn: bool, state_ranks: tuple):
    """Build (and cache) the jitted shard_map program for one (config, mesh)."""
    from jax.sharding import PartitionSpec as P

    state_specs = {k: P("streams", *([None] * (r - 1))) for k, r in state_ranks}

    @partial(jax.jit, donate_argnums=(0,))
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(state_specs, P(None, "streams", None), P(None, "streams")),
        out_specs=(state_specs, P(None, "streams")),
    )
    def run(state, values, ts_unix):
        return _scan_chunk(state, values, ts_unix, cfg, learn)

    return run


def sharded_chunk_step(state: dict, values: jnp.ndarray, ts_unix: jnp.ndarray,
                       cfg: ModelConfig, mesh, learn: bool = True):
    """:func:`chunk_step` under explicit SPMD (`jax.shard_map`) over the
    1-D ("streams",) mesh.

    Streams are independent, so each device steps its own shard with zero
    collectives — guaranteed by construction here, whereas plain jit +
    sharded inputs lets the partitioner all-gather around ops it won't
    partition (observed: the [G, C] TopK in SP inhibition gets its batch
    gathered to every chip). tests/scale/test_sharded.py pins the compiled
    program collective-free.
    """
    state_ranks = tuple(sorted((k, max(np.ndim(v), 1)) for k, v in state.items()))
    return _sharded_chunk_fn(cfg, mesh, learn, state_ranks)(state, values, ts_unix)


def replicate_state(state: dict, group_size: int) -> dict:
    """Tile a single-stream state dict into a [G, ...] group state (host side).

    Every stream starts from the same deterministic init (models/state.py);
    per-stream divergence comes entirely from the data, mirroring the
    reference's one-independent-model-per-stream registry (SURVEY.md C19).
    """
    return {
        k: np.broadcast_to(np.asarray(v)[None, ...], (group_size, *np.shape(v))).copy()
        for k, v in state.items()
    }


@partial(jax.jit, static_argnames=("group_size",))
def _broadcast_state(state: dict, group_size: int) -> dict:
    return {
        k: jnp.broadcast_to(v[None, ...], (group_size, *v.shape)) for k, v in state.items()
    }


def replicate_state_device(state: dict, group_size: int) -> dict:
    """Device-side :func:`replicate_state`: transfer ONE stream's state
    (~0.5 MB) and broadcast to [G, ...] on the chip.

    Host-side tiling + device_put stages the whole [G, ...] state on the
    host (13.9 GB at G=24576) and transfers it for what is a broadcast of
    identical rows; this makes group construction O(one stream) of
    transfer regardless of G.
    """
    single = {k: jnp.asarray(v) for k, v in state.items()}
    return _broadcast_state(single, group_size)


@partial(jax.jit, donate_argnums=(0,))
def _set_row_jit(state: dict, fresh: dict, slot: jnp.ndarray) -> dict:
    return jax.tree_util.tree_map(
        lambda s, f: s.at[slot].set(f.astype(s.dtype)), state, fresh)


def set_state_row(state: dict, fresh: dict, slot: int) -> dict:  # rtap: allow[twin-parity] — host twin is a one-line numpy row assignment; claim/release semantics pinned by tests/unit/test_dynamic_streams.py and the registry tests
    """Overwrite ONE stream's row of grouped [G, ...] state with a fresh
    single-stream state (dynamic slot claim — registry.claim_slot). The
    slot index is a traced argument so claiming different slots reuses one
    compiled program; the group buffer is donated (no [G, ...] copy).
    `fresh` comes in the group's form: for a group that holds the kernel's,
    the caller re-lays `init_state`'s public row on the host first
    (ops/resident.py:host_resident), one row's worth."""
    return _set_row_jit(state, {k: jnp.asarray(v) for k, v in fresh.items()},
                        jnp.asarray(slot, jnp.int32))


class TpuStepRunner:
    """Holds one stream's device state and steps it record by record.

    Used by `HTMModel(backend="tpu")` — the single-stream convenience path.
    High-throughput multi-stream execution goes through service/registry.py
    stream groups and :func:`chunk_step` instead. Like a group, the runner
    keeps the state in the kernel's form on the device (`resident`) and
    `state` reads as the public tree (ops/resident.py).
    """

    def __init__(self, cfg: ModelConfig, state: dict):
        from rtap_tpu.ops.resident import host_resident

        self.cfg = cfg
        self.relayouts = 0
        self.resident = jax.device_put(host_resident(state, cfg.tm, self))

    @property
    def state(self):
        from rtap_tpu.ops.resident import PublicState

        return PublicState(self)

    def step(self, values: np.ndarray, ts_unix: int, learn: bool = True):
        """-> raw score (float), or (raw, prediction, prob) floats when the
        SDR classifier is enabled (static per config)."""
        v = jnp.asarray(np.atleast_1d(values), jnp.float32)
        self.resident, out = fused_step(self.resident, v, jnp.int32(ts_unix), self.cfg, learn)
        if self.cfg.classifier.enabled:
            return float(out[0]), float(out[1]), float(out[2])
        return float(out)
