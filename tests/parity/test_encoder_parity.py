"""Oracle-vs-device parity for hashing + record encoding (SURVEY.md §4 item 2).

The RDSE/date encoder must be bit-identical across host numpy and jitted JAX:
every downstream parity test depends on both backends seeing the same SDR.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rtap_tpu.config import DateConfig, ModelConfig, RDSEConfig
from rtap_tpu.models.oracle.encoders import encode_record
from rtap_tpu.ops.encoders_tpu import bind_offsets, encode_device
from rtap_tpu.ops.hashing_tpu import hash_bits, hash_u32
from rtap_tpu.utils.hashing import hash_bits_np, hash_u32_np


def test_hash_u32_parity():
    keys = np.arange(-500, 500, dtype=np.int64)
    for seed in (0, 42, 0xDEADBEEF):
        np_h = hash_u32_np(keys, seed)
        dev_h = np.asarray(jax.jit(lambda k: hash_u32(k, seed))(jnp.asarray(keys, jnp.int32)))
        np.testing.assert_array_equal(np_h, dev_h)


def test_hash_bits_parity():
    keys = np.arange(-200, 200, dtype=np.int64)
    np_b = hash_bits_np(keys, 7, 400)
    dev_b = np.asarray(jax.jit(lambda k: hash_bits(k, 7, 400))(jnp.asarray(keys, jnp.int32)))
    np.testing.assert_array_equal(np_b, dev_b)


@pytest.mark.quick
@pytest.mark.parametrize("n_fields", [1, 3])
def test_encode_parity(n_fields):
    cfg = ModelConfig(
        rdse=RDSEConfig(size=100, active_bits=7, resolution=0.5),
        date=DateConfig(time_of_day_width=5, time_of_day_size=13, weekend_width=3),
        n_fields=n_fields,
    )
    rng = np.random.default_rng(0)
    offsets = rng.normal(size=n_fields).astype(np.float32)
    enc_dev = jax.jit(lambda v, t, o: encode_device(cfg, v, t, o))
    for i in range(50):
        values = (rng.normal(size=n_fields) * 10).astype(np.float32)
        if i % 7 == 0:
            values[rng.integers(n_fields)] = np.nan  # missing sample
        ts = int(rng.integers(0, 2_000_000_000))
        host = encode_record(cfg, values, ts, offsets)
        dev = np.asarray(enc_dev(jnp.asarray(values), jnp.int32(ts), jnp.asarray(offsets)))
        np.testing.assert_array_equal(host, dev, err_msg=f"record {i} ts={ts}")


def test_encode_parity_extreme_values():
    """Wild finite values (overflowed counters, sensor garbage) must encode
    identically on both backends: the shared RDSE_BUCKET_CLAMP keeps the
    device's int32 bucket from wrapping where the host's int64 would not."""
    cfg = ModelConfig(
        rdse=RDSEConfig(size=100, active_bits=7, resolution=0.5),
        date=DateConfig(time_of_day_width=0, time_of_day_size=0, weekend_width=0),
    )
    offsets = np.zeros(1, np.float32)
    enc_dev = jax.jit(lambda v, t, o: encode_device(cfg, v, t, o))
    for x in (3e9, -3e9, 1e12, 1e30, -1e30, 3.4e38):
        values = np.asarray([x], np.float32)
        host = encode_record(cfg, values, 0, offsets)
        dev = np.asarray(enc_dev(jnp.asarray(values), jnp.int32(0), jnp.asarray(offsets)))
        np.testing.assert_array_equal(host, dev, err_msg=f"value {x}")


def test_bind_offsets_matches_host_rule():
    values = jnp.asarray([np.nan, 2.5, 7.0], jnp.float32)
    off = jnp.zeros(3, jnp.float32)
    bound = jnp.asarray([False, False, True])
    new_off, new_bound = jax.jit(bind_offsets)(values, off, bound)
    # field0: NaN -> stays unbound; field1: binds to 2.5; field2: already bound
    np.testing.assert_array_equal(np.asarray(new_bound), [False, True, True])
    np.testing.assert_allclose(np.asarray(new_off), [0.0, 2.5, 0.0])


def test_scalar_encoder_parity_and_properties():
    """Classic ScalarEncoder (SURVEY.md C2): host/device bit-identical, and
    the classic properties hold — nearby values share bits proportionally to
    distance, out-of-range values clip to the edge runs."""
    import jax.numpy as jnp
    import numpy as np

    from rtap_tpu.config import ModelConfig, ScalarEncoderConfig
    from rtap_tpu.models.oracle.encoders import encode_record
    from rtap_tpu.ops.encoders_tpu import encode_device

    cfg = ModelConfig(scalar=ScalarEncoderConfig(size=100, width=9,
                                                 min_val=0.0, max_val=50.0))
    assert cfg.input_size == 100 + cfg.date.size
    off = np.zeros(1, np.float32)
    sdrs = {}
    for v in (-5.0, 0.0, 1.0, 25.0, 26.0, 49.9, 50.0, 75.0, float("nan")):
        host = encode_record(cfg, np.array([v]), 1_700_000_000, off)
        dev = np.asarray(
            encode_device(cfg, jnp.float32([v]), jnp.int32(1_700_000_000),
                          jnp.asarray(off))
        )
        np.testing.assert_array_equal(host, dev, err_msg=str(v))
        sdrs[v] = host[:100]
    w = 9
    assert sdrs[25.0].sum() == w
    # adjacent buckets overlap in w-1 bits; distance decays overlap
    assert (sdrs[25.0] & sdrs[26.0]).sum() in (w - 2, w - 1)
    assert (sdrs[1.0] & sdrs[49.9]).sum() == 0
    # clipping: out-of-range == edge encodings; NaN encodes nothing
    np.testing.assert_array_equal(sdrs[-5.0], sdrs[0.0])
    np.testing.assert_array_equal(sdrs[75.0], sdrs[50.0])
    nan_sdr = encode_record(cfg, np.array([np.nan]), 1_700_000_000, off)
    assert nan_sdr[:100].sum() == 0
    # full pipeline compiles with the scalar encoder selected
    from rtap_tpu.models.htm_model import HTMModel

    m_cpu = HTMModel(cfg, seed=2, backend="cpu")
    m_dev = HTMModel(cfg, seed=2, backend="tpu")
    for i in range(30):
        v = 25.0 + 10.0 * np.sin(i / 3)
        r1 = m_cpu.run(1_700_000_000 + i, v)
        r2 = m_dev.run(1_700_000_000 + i, v)
        assert r1.raw_score == r2.raw_score, i


# ---- the SDR at the cases an index write hid (ISSUE 31) -------------------
# Every bit index is compared against the input iota now (`_bits_at`), where
# it was `sdr.at[idx].set(True, mode="drop")`: a dropped index (a missing
# field, a weekday), an index named twice (colliding hash bits), a
# time-of-day run that wraps at the ring's end, the weekend bits on and off.

_MIDNIGHT = 19_676 * 86_400  # 2023-11-15 00:00:00 UTC, a Wednesday
_STAMPS = {
    "ring_start": _MIDNIGHT,              # centre 0: the run reaches back over the end
    "ring_end": _MIDNIGHT + 86_399,       # centre size-1: it runs over into the start
    "saturday": _MIDNIGHT + 3 * 86_400 + 43_200,
    "sunday_last_second": _MIDNIGHT + 4 * 86_400 + 86_399,
    "monday_first_second": _MIDNIGHT + 5 * 86_400,
}
_VALUES = {
    "all_finite": [3.25, -17.5, 40.0],
    "nan_first": [np.nan, -17.5, 40.0],
    "inf_middle": [3.25, np.inf, 40.0],
    "neg_inf_last": [3.25, -17.5, -np.inf],
    "all_missing": [np.nan, np.inf, -np.inf],
}


@functools.lru_cache(maxsize=None)
def _edge_encoder(family: str):
    """(cfg, jitted encode_device) of a family: one compile serves its cases."""
    cfg = _edge_cfg(family)
    return cfg, jax.jit(lambda *a: encode_device(cfg, *a))


def _edge_cfg(family: str) -> ModelConfig:
    from rtap_tpu.config import (
        CompositeEncoderConfig, FieldSpec, ScalarEncoderConfig,
    )

    date = DateConfig(time_of_day_width=5, time_of_day_size=13, weekend_width=3)
    if family == "rdse":
        return ModelConfig(rdse=RDSEConfig(size=100, active_bits=7, resolution=0.5),
                           date=date, n_fields=3)
    if family == "rdse_colliding":  # 11 bits hashed into 12 places: they collide
        return ModelConfig(rdse=RDSEConfig(size=12, active_bits=11, resolution=0.5),
                           date=date, n_fields=3)
    if family == "rdse_no_date":
        return ModelConfig(rdse=RDSEConfig(size=100, active_bits=7, resolution=0.5),
                           date=DateConfig(time_of_day_width=0, time_of_day_size=0,
                                           weekend_width=0), n_fields=3)
    if family == "scalar":
        return ModelConfig(scalar=ScalarEncoderConfig(size=60, width=9, min_val=-20.0,
                                                      max_val=50.0),
                           date=date, n_fields=3)
    if family == "composite":
        return ModelConfig(n_fields=3, date=date, composite=CompositeEncoderConfig(fields=(
            FieldSpec(name="v", kind="rdse", size=96, active_bits=9, resolution=0.5, seed=3),
            FieldSpec(name="d", kind="delta", size=10, active_bits=7, resolution=0.25, seed=3),
            FieldSpec(name="c", kind="categorical", size=80, active_bits=5, seed=3))))
    raise AssertionError(family)


@pytest.mark.parametrize("stamp", sorted(_STAMPS))
@pytest.mark.parametrize("values", sorted(_VALUES))
@pytest.mark.parametrize("family", ["rdse", "rdse_colliding", "rdse_no_date",
                                    "scalar", "composite"])
def test_encode_edges_match_the_oracle(family, values, stamp):
    cfg, encode = _edge_encoder(family)
    v, ts = np.asarray(_VALUES[values], np.float32), _STAMPS[stamp]
    off = np.asarray([0.5, -1.0, 2.0], np.float32)
    extra = ()
    if family == "composite":
        extra = (np.asarray(cfg.field_resolutions(), np.float32),
                 np.asarray([1.0, 2.0, np.nan], np.float32))
    host = encode_record(cfg, v.astype(np.float64), ts, off, *extra)
    dev = np.asarray(encode(jnp.asarray(v), jnp.int32(ts), jnp.asarray(off),
                            *map(jnp.asarray, extra)))
    np.testing.assert_array_equal(host, dev)

    layout = cfg.field_layout()  # the value fields; the date bits follow them
    for f, (name, _kind, o, sz) in enumerate(layout):
        if not np.isfinite(v[f]):  # a missing sample sets no bit of its field
            assert not dev[o:o + sz].any(), name
    if values == "all_finite" and family == "rdse_colliding":
        _, _, o, sz = layout[0]
        assert 0 < dev[o:o + sz].sum() < cfg.rdse.active_bits  # bits did collide
    base = layout[-1][2] + layout[-1][3]
    if family == "rdse_no_date":
        assert dev.shape == (base,)
        return
    ring = dev[base:base + cfg.date.time_of_day_size]
    assert ring.sum() == cfg.date.time_of_day_width
    if stamp in ("ring_start", "ring_end"):
        assert ring[0] and ring[-1]  # the run wrapped
    weekend = dev[base + cfg.date.time_of_day_size:]
    assert weekend.shape == (cfg.date.weekend_width,)
    assert weekend.all() == weekend.any() == (stamp in ("saturday", "sunday_last_second"))
