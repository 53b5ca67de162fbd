"""SDR classifier (SURVEY.md C10): oracle-vs-device parity + prediction
quality. The classifier decodes TM active cells to a predicted next value —
the "prediction" half of the reference's name; quality bar: on a periodic
stream it must beat the last-value baseline once trained."""

import numpy as np
import pytest

from rtap_tpu.config import (
    ClassifierConfig,
    DateConfig,
    LikelihoodConfig,
    ModelConfig,
    RDSEConfig,
    SPConfig,
    TMConfig,
)
from rtap_tpu.models.htm_model import HTMModel


def _cfg(buckets=33, alpha=0.1):
    return ModelConfig(
        rdse=RDSEConfig(size=128, active_bits=9, resolution=1.0),
        date=DateConfig(time_of_day_width=0, time_of_day_size=0, weekend_width=0),
        sp=SPConfig(columns=128, num_active_columns=8),
        tm=TMConfig(cells_per_column=8, activation_threshold=4, min_threshold=2,
                    max_segments_per_cell=4, max_synapses_per_segment=12,
                    new_synapse_count=6, learn_cap=48, col_cap=8),
        likelihood=LikelihoodConfig(mode="streaming", learning_period=20,
                                    estimation_samples=10),
        classifier=ClassifierConfig(enabled=True, buckets=buckets, alpha=alpha),
    )


def _periodic_values(n, period=6, unique=False):
    if unique:
        cycle = np.array([10.0, 13.0, 17.0, 22.0, 19.0, 15.0], np.float32)[:period]
    else:
        # 14 and 18 each appear twice with different successors — requires
        # TM context disambiguation (the hard case)
        cycle = np.array([10.0, 14.0, 18.0, 22.0, 18.0, 14.0], np.float32)[:period]
    return np.tile(cycle, n // period + 1)[:n]


@pytest.mark.parametrize("rows", ["wide", "narrow"])
def test_classifier_parity_cpu_vs_device(rows, monkeypatch):
    """Same records through the numpy oracle and the jitted device kernel:
    predictions agree to float tolerance (softmax exp may differ by ulps).
    Covered in both forms of the TM step — the classifier consumes TM cell
    state (prev_active), which the flat adapters must hand over unchanged."""
    import jax

    import rtap_tpu.ops.tm_tpu as tm_tpu

    cfg = _cfg()
    cpu = HTMModel(cfg, seed=1, backend="cpu")
    # the line between the forms, moved under this shape (read at trace time)
    monkeypatch.setattr(tm_tpu, "WIDE_ROW_LANES", 1 if rows == "wide" else 1 << 30)
    jax.clear_caches()
    try:
        dev = HTMModel(cfg, seed=1, backend="tpu")
        vals = _periodic_values(200)
        for i, v in enumerate(vals):
            rc = cpu.run(1_700_000_000 + i, float(v))
            rd = dev.run(1_700_000_000 + i, float(v))
            assert rc.raw_score == pytest.approx(rd.raw_score, abs=0.0), f"step {i}"
            assert rc.prediction == pytest.approx(rd.prediction, rel=1e-4, abs=1e-4), f"step {i}"
            assert rc.prediction_prob == pytest.approx(rd.prediction_prob, rel=1e-3, abs=1e-5), f"step {i}"
    finally:
        jax.clear_caches()


def _prediction_maes(vals, train=400):
    cfg = _cfg()
    model = HTMModel(cfg, seed=0, backend="cpu")
    preds, actual_next, last_vals = [], [], []
    for i, v in enumerate(vals[:-1]):
        res = model.run(1_700_000_000 + i, float(v))
        if i >= train:
            preds.append(res.prediction)
            actual_next.append(float(vals[i + 1]))
            last_vals.append(float(v))
    mae_model = np.mean(np.abs(np.array(preds) - np.array(actual_next)))
    mae_last = np.mean(np.abs(np.array(last_vals) - np.array(actual_next)))
    return mae_model, mae_last


def test_classifier_near_exact_on_unique_cycle():
    """Unique-successor cycle: TM predicts every transition, so the decoded
    next value must be near-exact — and far better than last-value."""
    mae_model, mae_last = _prediction_maes(_periodic_values(600, unique=True))
    assert mae_model < 0.25, mae_model
    assert mae_model < 0.1 * mae_last, (mae_model, mae_last)


def test_classifier_beats_last_value_on_ambiguous_cycle():
    """Shared-element cycle (14/18 appear twice with different successors):
    the vanilla TM does not fully disambiguate every context (the behavior
    NuPIC's backtracking TM targets — SURVEY.md C6), but the decoded
    prediction must still beat the last-value baseline."""
    mae_model, mae_last = _prediction_maes(_periodic_values(600))
    assert mae_model < 0.8 * mae_last, (mae_model, mae_last)


def test_classifier_bucket_clamps_and_handles_nan():
    from rtap_tpu.models.oracle.classifier import classifier_bucket

    assert classifier_bucket(0.0, 0.0, 1.0, 33) == 16
    assert classifier_bucket(5.0, 0.0, 1.0, 33) == 21
    assert classifier_bucket(1e9, 0.0, 1.0, 33) == 32  # clamp high
    assert classifier_bucket(-1e9, 0.0, 1.0, 33) == 0  # clamp low
    assert classifier_bucket(float("nan"), 0.0, 1.0, 33) == 16  # NaN -> center


def test_classifier_group_and_replay_predictions():
    """Stream groups surface predictions on both backends; replay collects
    them into ReplayResult.predictions."""
    from rtap_tpu.data.synthetic import SyntheticStreamConfig, generate_cluster
    from rtap_tpu.service.loop import replay_streams
    from rtap_tpu.service.registry import StreamGroup

    cfg = _cfg()
    ids = ["a", "b"]
    tpu = StreamGroup(cfg, ids, backend="tpu")
    cpu = StreamGroup(cfg, ids, backend="cpu")
    vals = _periodic_values(80)
    for i in range(80):
        v = np.array([vals[i], vals[i] + 1], np.float32)
        rt = tpu.tick(v, 1_700_000_000 + i)
        rc = cpu.tick(v, 1_700_000_000 + i)
        assert rt.prediction is not None and rc.prediction is not None
        np.testing.assert_allclose(rt.prediction, rc.prediction, rtol=1e-4, atol=1e-4)

    scfg = SyntheticStreamConfig(length=60, cadence_s=1.0, n_anomalies=0)
    streams = generate_cluster(2, metrics=("cpu",), cfg=scfg, seed=3)
    res = replay_streams(streams, cfg, backend="tpu", chunk_ticks=30)
    assert res.predictions is not None and res.predictions.shape == (60, 2)
    assert np.isfinite(res.predictions).all()
