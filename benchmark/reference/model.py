"""One stream of the reference: bind -> encode -> SP -> TM -> raw score.

`record_step` is rtap_tpu/models/htm_model.py:oracle_record_step without the
classifier branch (no benchmark configuration enables it)."""

from __future__ import annotations

import numpy as np

from benchmark.reference.config import ModelConfig
from benchmark.reference.encoders import encode_record
from benchmark.reference.spatial_pooler import sp_compute
from benchmark.reference.state import init_state
from benchmark.reference.temporal_memory import TMOracle


def record_step(cfg: ModelConfig, state: dict, tm: TMOracle,
                values: np.ndarray, ts_unix: int, learn: bool = True) -> float:
    bind = ~state["enc_bound"] & np.isfinite(values)
    if bind.any():
        # bind each field's offset at its first finite value (a leading NaN
        # must not poison the stream's bucket arithmetic forever)
        state["enc_offset"] = np.where(
            bind, values, state["enc_offset"]).astype(np.float32)
        state["enc_bound"] = state["enc_bound"] | bind
    sdr = encode_record(cfg, values, int(ts_unix), state["enc_offset"],
                        state["enc_resolution"], None)
    active = sp_compute(state, sdr, cfg.sp, learn)
    return tm.compute(active, learn)


class ReferenceStream:
    """The reference model of one metric stream, from `seed`."""

    def __init__(self, cfg: ModelConfig, seed: int):
        if cfg.classifier.enabled or cfg.cadence_active or (
                cfg.composite is not None and cfg.composite.has_delta):
            raise ValueError("the benchmark's reference has no SDR classifier, "
                             "no learning cadence and no delta field")
        self.cfg = cfg
        self.state = init_state(cfg, seed, include_fwd=False)
        self._tm = TMOracle(self.state, cfg.tm)

    def run(self, ts_unix: int, value) -> float:
        """Score one record — a scalar, or the `[n_fields]` row of a
        multi-field model (NaN = missing sample) — learning on."""
        return float(record_step(
            self.cfg, self.state, self._tm,
            np.atleast_1d(np.asarray(value, np.float32)), int(ts_unix), True))
