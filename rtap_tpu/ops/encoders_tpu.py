"""Device-side record encoder: RDSE + date bits, table-free, vmappable.

Twin of models/oracle/encoders.py (SURVEY.md C1/C2). The RDSE is a pure hash
function (bucket b -> bits {hash(seed, b+k) % n}), so encoding runs on device
with no host-side bucket table: one record is (values[F] f32, ts i32) and the
output is a bool[input_size] SDR, each bit index compared against the input
iota (:func:`_bits_at`). All arithmetic is f32/int32 and bit-identical to the
host oracle (tests/parity/test_encoder_parity.py).

NaN/inf field values contribute no bits (NuPIC missing-sample behavior),
implemented branch-free: their indices point at input_size, which equals no
position of the iota.
"""

from __future__ import annotations

import jax.numpy as jnp

from rtap_tpu.config import RDSE_BUCKET_CLAMP, ModelConfig
from rtap_tpu.ops.hashing_tpu import hash_bits

SECONDS_PER_DAY = 86400
_EPOCH_WEEKDAY_SHIFT = 3  # 1970-01-01 was a Thursday; weekday = (days+3) % 7


def _bits_at(idx: jnp.ndarray, n_in: int) -> jnp.ndarray:
    """bool[n_in] with a True at every position named in `idx` (any shape,
    i32). An index of n_in or more names no position (a missing field, a
    weekday), and an index named twice (colliding RDSE bits) is an OR. A
    compare grid [len(idx), n_in] folded by `any`, not `.at[idx].set(True)`:
    the element-wise scatter costs 5-7 ns an index on a v5e (PERF.md s6)."""
    return (idx.reshape(-1)[:, None] == jnp.arange(n_in, dtype=jnp.int32)).any(0)


def _composite_indices(
    cfg: ModelConfig,
    values: jnp.ndarray,  # [F] f32
    enc_offset: jnp.ndarray,  # [F] f32
    enc_resolution: jnp.ndarray,  # [F] f32
    enc_prev: jnp.ndarray | None,  # [F] f32 (delta predecessor), or None
) -> jnp.ndarray:
    """Composite-family bit indices (ISSUE 9), flattened across fields;
    missing samples point at n_in (no bit). Static python loop over the
    FieldSpec table — F is small and the per-field geometry (size, kind,
    seed, offset) is config-static, so this traces to straight-line code.
    Twin of the oracle's _composite_field_bits, bit-exact per field."""
    n_in = cfg.input_size
    parts = []
    for f, (spec, (_name, _kind, off, _sz)) in enumerate(
            zip(cfg.composite.fields, cfg.field_layout())):
        w = spec.active_bits
        vf = values[f]
        res = enc_resolution[f].astype(jnp.float32)
        finite = jnp.isfinite(vf)
        v = jnp.where(finite, vf, jnp.float32(0.0))
        if spec.kind == "delta":
            # first difference; a stream's first sample (prev NaN) has
            # none — same missing-sample drop as a NaN value
            pf = enc_prev[f] if enc_prev is not None else jnp.float32(jnp.nan)
            finite = finite & jnp.isfinite(pf)
            p = jnp.where(jnp.isfinite(pf), pf, jnp.float32(0.0))
            bucket = jnp.clip(jnp.round((v - p) / res),
                              -RDSE_BUCKET_CLAMP,
                              RDSE_BUCKET_CLAMP).astype(jnp.int32)
            keys = bucket + jnp.arange(w, dtype=jnp.int32)
        elif spec.kind == "categorical":
            # rounded id, clamped FIRST in the f32 bucket domain (shared
            # rdse_bucket arithmetic), then in the integer domain to the
            # per-field categorical bound so c*w + k cannot wrap int32 —
            # the same double clamp the host performs
            b = jnp.clip(jnp.round(v / res), -RDSE_BUCKET_CLAMP,
                         RDSE_BUCKET_CLAMP).astype(jnp.int32)
            cclamp = jnp.int32(spec.categorical_clamp())
            cat = jnp.clip(b, -cclamp, cclamp)
            keys = cat * jnp.int32(w) + jnp.arange(w, dtype=jnp.int32)
        else:  # rdse
            bucket = jnp.clip(jnp.round((v - enc_offset[f]) / res),
                              -RDSE_BUCKET_CLAMP,
                              RDSE_BUCKET_CLAMP).astype(jnp.int32)
            keys = bucket + jnp.arange(w, dtype=jnp.int32)
        bits = hash_bits(keys, jnp.uint32(spec.seed)
                         + jnp.uint32(0x1000) * jnp.uint32(f), spec.size)
        idx = bits + jnp.int32(off)
        parts.append(jnp.where(finite, idx, n_in))
    return jnp.concatenate(parts)


# rtap: twin[encode_record] — the host oracle encoder (oracle/encoders.py)
def encode_device(
    cfg: ModelConfig,
    values: jnp.ndarray,  # [F] f32
    ts_unix: jnp.ndarray,  # scalar i32
    enc_offset: jnp.ndarray,  # [F] f32
    enc_resolution: jnp.ndarray | None = None,  # [F] f32 (runtime, per stream)
    enc_prev: jnp.ndarray | None = None,  # [F] f32 (delta fields' predecessor)
) -> jnp.ndarray:
    """Encode one record -> bool[input_size]. Layout matches the oracle:
    [field0 | field1 | ... | time-of-day ring | weekend] per
    cfg.field_layout() (uniform RDSE/scalar, or the composite family's
    per-field kinds).

    `enc_resolution` defaults to the config's static resolution (rounded
    through f32, exactly like the state-carried per-stream array)."""
    F = cfg.n_fields
    n_in = cfg.input_size
    if cfg.composite is not None:
        if enc_resolution is None:
            enc_resolution = jnp.asarray(cfg.field_resolutions(), jnp.float32)
        idx = _composite_indices(cfg, values, enc_offset, enc_resolution,
                                 enc_prev)
        sdr = _bits_at(idx, n_in)
        base = cfg.composite.size
        if cfg.date.time_of_day_width:
            center = (ts_unix % SECONDS_PER_DAY) * cfg.date.time_of_day_size \
                // SECONDS_PER_DAY
            tod = (
                center
                + jnp.arange(cfg.date.time_of_day_width, dtype=jnp.int32)
                - cfg.date.time_of_day_width // 2
            ) % cfg.date.time_of_day_size
            sdr = sdr | _bits_at(base + tod, n_in)
            base += cfg.date.time_of_day_size
        if cfg.date.weekend_width:
            weekend = ((ts_unix // SECONDS_PER_DAY + _EPOCH_WEEKDAY_SHIFT)
                       % 7) >= 5
            widx = jnp.where(
                weekend,
                base + jnp.arange(cfg.date.weekend_width, dtype=jnp.int32),
                n_in)
            sdr = sdr | _bits_at(widx, n_in)
        return sdr
    R = cfg.field_size
    finite = jnp.isfinite(values)
    v = jnp.where(finite, values, jnp.float32(0.0))

    if cfg.scalar is not None:
        # classic ScalarEncoder: clipped fixed-range bucket, contiguous run
        sc = cfg.scalar
        vc = jnp.clip(v, jnp.float32(sc.min_val), jnp.float32(sc.max_val))
        scale = jnp.float32(sc.size - sc.width) / (
            jnp.float32(sc.max_val) - jnp.float32(sc.min_val)
        )
        bucket = jnp.round((vc - jnp.float32(sc.min_val)) * scale).astype(jnp.int32)
        bits = bucket[:, None] + jnp.arange(sc.width, dtype=jnp.int32)[None, :]
    else:
        w = cfg.rdse.active_bits
        if enc_resolution is None:
            enc_resolution = jnp.full(F, jnp.float32(cfg.rdse.resolution))
        bucket = jnp.clip(
            jnp.round((v - enc_offset) / enc_resolution.astype(jnp.float32)),
            -RDSE_BUCKET_CLAMP,
            RDSE_BUCKET_CLAMP,
        ).astype(jnp.int32)
        keys = bucket[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]  # [F, w]
        # per-field hash stream: seed + 0x1000 * field (same keying as oracle)
        seeds = jnp.uint32(cfg.rdse.seed) + jnp.uint32(0x1000) * jnp.arange(F, dtype=jnp.uint32)
        bits = hash_bits(keys, seeds[:, None], R)  # [F, w]
    idx = bits + (jnp.arange(F, dtype=jnp.int32) * R)[:, None]
    idx = jnp.where(finite[:, None], idx, n_in)  # missing field -> no bit

    sdr = _bits_at(idx, n_in)

    base = F * R
    if cfg.date.time_of_day_width:
        # integer floor((s/86400) * ring_size); identical to the oracle
        center = (ts_unix % SECONDS_PER_DAY) * cfg.date.time_of_day_size // SECONDS_PER_DAY
        tod = (
            center
            + jnp.arange(cfg.date.time_of_day_width, dtype=jnp.int32)
            - cfg.date.time_of_day_width // 2
        ) % cfg.date.time_of_day_size
        sdr = sdr | _bits_at(base + tod, n_in)
        base += cfg.date.time_of_day_size
    if cfg.date.weekend_width:
        weekend = ((ts_unix // SECONDS_PER_DAY + _EPOCH_WEEKDAY_SHIFT) % 7) >= 5
        widx = jnp.where(weekend, base + jnp.arange(cfg.date.weekend_width, dtype=jnp.int32), n_in)
        sdr = sdr | _bits_at(widx, n_in)
    return sdr


# rtap: twin[oracle_record_step] — the oracle performs the first-finite
# bind inline (models/htm_model.py, the np.where on enc_offset)
def bind_offsets(
    values: jnp.ndarray, enc_offset: jnp.ndarray, enc_bound: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Bind each field's RDSE offset at its first finite value (NuPIC binds
    buckets to the first sample; a leading NaN must not poison the stream).
    Returns (new_offset, new_bound); pure, runs inside the fused step."""
    bind = ~enc_bound & jnp.isfinite(values)
    return jnp.where(bind, values, enc_offset), enc_bound | bind
