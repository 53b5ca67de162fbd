"""Host-side encoders: RDSE + date (time-of-day / weekend) + multi-field.

Semantics per SURVEY.md C1/C2 (NuPIC `random_distributed_scalar.py`,
`date.py`, `multi.py`), redesigned table-free: RDSE bucket b activates bits
{hash(seed, b+k) % n : k < w}, so adjacent buckets share w-1 hash keys and
SDR overlap decays linearly with |Δbucket| — the defining RDSE property —
with no host-side bucket map to grow or serialize. Identical arithmetic runs
on-device in ops/encoders_tpu.py.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.config import (
    RDSE_BUCKET_CLAMP,
    DateConfig,
    FieldSpec,
    ModelConfig,
    RDSEConfig,
    ScalarEncoderConfig,
)
from benchmark.reference.hashing import hash_bits_np

SECONDS_PER_DAY = 86400
# Unix epoch (1970-01-01) was a Thursday; weekday = (days + 3) % 7 (Mon=0).
_EPOCH_WEEKDAY_SHIFT = 3


def rdse_bucket(value: float | np.ndarray, offset: float | np.ndarray, resolution: float) -> np.ndarray:
    """Bucket index: round((value - offset) / resolution). NuPIC binds `offset`
    to the first value a stream sees so buckets stay centered on the data.

    Computed in float32 end-to-end: the device kernels have no f64 (JAX x64
    stays off on TPU), and host/device bucket arithmetic must be bit-identical
    for oracle-vs-TPU parity (SURVEY.md §4 item 2)."""
    v = np.asarray(value, np.float32)
    off = np.asarray(offset, np.float32)
    res = np.float32(resolution)
    # f32 divide may overflow to inf for wild values; that's fine — inf clamps
    # to the bound, same as on device (which warns for nothing).
    with np.errstate(over="ignore"):
        b = np.clip(np.round((v - off) / res), -RDSE_BUCKET_CLAMP, RDSE_BUCKET_CLAMP)
    return b.astype(np.int64)


def rdse_bits(cfg: RDSEConfig, bucket: int, field_index: int = 0) -> np.ndarray:
    """Active bit indices for one bucket (may contain duplicates — tolerated,
    see RDSEConfig docstring). Each field of a multivariate record gets its
    own hash stream via the seed."""
    keys = bucket + np.arange(cfg.active_bits, dtype=np.int64)
    return hash_bits_np(keys, cfg.seed + 0x1000 * field_index, cfg.size)


def scalar_bucket(value: float | np.ndarray, cfg: ScalarEncoderConfig) -> np.ndarray:
    """Classic ScalarEncoder bucket (SURVEY.md C2): clip into [min, max],
    then round((v - min) * (size - width) / range). All-f32 so the device
    twin is bit-identical (same contract as rdse_bucket)."""
    v = np.clip(np.asarray(value, np.float32), np.float32(cfg.min_val), np.float32(cfg.max_val))
    scale = np.float32(cfg.size - cfg.width) / (np.float32(cfg.max_val) - np.float32(cfg.min_val))
    return np.round((v - np.float32(cfg.min_val)) * scale).astype(np.int64)


def scalar_bits(cfg: ScalarEncoderConfig, bucket: int) -> np.ndarray:
    """Contiguous ``width``-bit run starting at the bucket index."""
    return bucket + np.arange(cfg.width)


def categorical_bits(spec: FieldSpec, category: int,
                     field_index: int = 0) -> np.ndarray:
    """Active bit indices for one category id (ISSUE 9 encoder family).

    Unlike the RDSE, distinct categories must NOT look similar: category
    ``c`` uses hash keys ``[c*w, c*w + w)`` — disjoint key ranges, so any
    SDR overlap between two ids is pure hash coincidence (the categorical
    property of "Encoding Data for HTM Systems"). Ids are clamped to
    ``spec.categorical_clamp()`` so the device's int32 ``c*w + k`` can
    never wrap where this host int64 path would not."""
    w = spec.active_bits
    clamp = spec.categorical_clamp()
    c = int(np.clip(category, -clamp, clamp))
    keys = c * w + np.arange(w, dtype=np.int64)
    return hash_bits_np(keys, spec.seed + 0x1000 * field_index, spec.size)


def _composite_field_bits(spec: FieldSpec, f: int, value: float, prev: float,
                          offset: float, resolution: float) -> np.ndarray | None:
    """One composite field's active bits (field base offset not yet
    applied), or None for a missing sample. The bucket arithmetic is the
    shared f32 rdse_bucket; what differs per kind is the encoded quantity
    (value vs first difference vs category id), the bucket center (bound
    offset for rdse; the natural 0 for delta/categorical), and the key
    derivation (overlapping runs vs disjoint categorical ranges)."""
    if not np.isfinite(value):
        return None
    if spec.kind == "delta":
        # NuPIC DeltaEncoder: the signal is the first difference; the
        # first sample of a stream (prev is NaN) has none -> missing
        if not np.isfinite(prev):
            return None
        d = float(np.float32(value) - np.float32(prev))
        b = int(rdse_bucket(d, 0.0, resolution))
        keys = b + np.arange(spec.active_bits, dtype=np.int64)
        return hash_bits_np(keys, spec.seed + 0x1000 * f, spec.size)
    if spec.kind == "categorical":
        cat = int(rdse_bucket(value, 0.0, resolution))  # res 1.0: round(id)
        return categorical_bits(spec, cat, f)
    # rdse: same arithmetic as the uniform family, per-field geometry;
    # the offset binds at the stream's first finite value like every RDSE
    b = int(rdse_bucket(value, offset, resolution))
    keys = b + np.arange(spec.active_bits, dtype=np.int64)
    return hash_bits_np(keys, spec.seed + 0x1000 * f, spec.size)


def time_of_day_bits(cfg: DateConfig, ts_unix: int) -> np.ndarray:
    """Periodic encoder over the 24h ring: w contiguous (wrapping) bits
    centered on the current time of day."""
    # Pure integer math (floor((s/86400) * size)) so host and device agree
    # exactly; float forms can differ by 1 ulp at bucket boundaries.
    center = (ts_unix % SECONDS_PER_DAY) * cfg.time_of_day_size // SECONDS_PER_DAY
    return (center + np.arange(cfg.time_of_day_width) - cfg.time_of_day_width // 2) % cfg.time_of_day_size


def is_weekend(ts_unix: int) -> bool:
    weekday = (ts_unix // SECONDS_PER_DAY + _EPOCH_WEEKDAY_SHIFT) % 7
    return weekday >= 5


def encode_record(
    cfg: ModelConfig,
    values: np.ndarray,
    ts_unix: int,
    enc_offset: np.ndarray,
    enc_resolution: np.ndarray | None = None,
    enc_prev: np.ndarray | None = None,
) -> np.ndarray:
    """Encode one record (n_fields scalars + timestamp) -> bool[input_size].

    Layout: [field0 | field1 | ... | time-of-day ring | weekend], each
    field's bit range per ``cfg.field_layout()`` (uniform RDSE/scalar
    runs, or the composite family's per-field kinds — ISSUE 9).
    ``enc_prev`` is the per-field previous finite value (delta fields
    only; None reads as "no predecessor yet" for every field).
    """
    sdr = np.zeros(cfg.input_size, bool)
    values = np.atleast_1d(np.asarray(values, np.float64))
    if len(values) != cfg.n_fields:
        raise ValueError(f"expected {cfg.n_fields} field value(s), got {len(values)}")
    if cfg.composite is not None:
        defaults = cfg.field_resolutions()
        for f, (spec, (_n, _k, off, _sz)) in enumerate(
                zip(cfg.composite.fields, cfg.field_layout())):
            res = float(np.float32(defaults[f])) if enc_resolution is None \
                else float(enc_resolution[f])
            prev = float(enc_prev[f]) if enc_prev is not None else float("nan")
            bits = _composite_field_bits(
                spec, f, float(values[f]), prev, float(enc_offset[f]), res)
            if bits is not None:
                sdr[off + bits] = True
        base = cfg.composite.size
        if cfg.date.time_of_day_width:
            sdr[base + time_of_day_bits(cfg.date, ts_unix)] = True
            base += cfg.date.time_of_day_size
        if cfg.date.weekend_width and is_weekend(ts_unix):
            sdr[base : base + cfg.date.weekend_width] = True
        return sdr
    for f in range(cfg.n_fields):
        if not np.isfinite(values[f]):
            continue  # missing/garbled sample -> no bits for this field (NuPIC behavior)
        if cfg.scalar is not None:
            b = int(scalar_bucket(values[f], cfg.scalar))
            sdr[f * cfg.field_size + scalar_bits(cfg.scalar, b)] = True
            continue
        # Always round the resolution through f32: the state-carried array is
        # f32, and the two entry points (explicit array vs config default)
        # must agree on bucket assignment at boundaries.
        res = float(np.float32(cfg.rdse.resolution)) if enc_resolution is None else float(enc_resolution[f])
        b = int(rdse_bucket(values[f], float(enc_offset[f]), res))
        sdr[f * cfg.field_size + rdse_bits(cfg.rdse, b, f)] = True
    base = cfg.n_fields * cfg.field_size
    if cfg.date.time_of_day_width:
        sdr[base + time_of_day_bits(cfg.date, ts_unix)] = True
        base += cfg.date.time_of_day_size
    if cfg.date.weekend_width:
        if is_weekend(ts_unix):
            sdr[base : base + cfg.date.weekend_width] = True
    return sdr
