"""HTMModel / AnomalyDetector factory — the plugin boundary.

This is the analog of the reference's `ModelFactory.create(modelParams)` ->
`HTMPredictionModel.run(record)` -> `inferences["anomalyScore"]` surface
(SURVEY.md C9, §3.1-3.2), which BASELINE.json designates as the plugin seam:
the CPU path is the default backend and TPU is opt-in. `backend="cpu"` runs
the numpy oracle in this process; `backend="tpu"` routes the SDR hot loop
through the jitted device step (ops/), keeping likelihood on host.

Single-stream convenience API; high-throughput multi-stream execution goes
through service/registry.py stream groups instead.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from rtap_tpu.config import ModelConfig, nab_preset
from rtap_tpu.models.oracle.encoders import encode_record
from rtap_tpu.models.oracle.likelihood import AnomalyLikelihood
from rtap_tpu.models.oracle.spatial_pooler import sp_compute
from rtap_tpu.models.oracle.temporal_memory import TMOracle
from rtap_tpu.models.state import init_state

BACKENDS = ("cpu", "tpu")


def oracle_record_step(
    cfg: ModelConfig,
    state: dict,
    tm: TMOracle,
    values: np.ndarray,
    ts_unix: int,
    learn: bool = True,
    classifier=None,
) -> float | tuple[float, float, float]:
    """One oracle record through bind -> encode -> SP -> TM -> raw score.

    The single source of the CPU per-record composition, shared by
    HTMModel.run and the service layer's CPU stream groups; the device twin
    is ops/step._step_impl. With a `classifier` (SDRClassifierOracle), also
    decodes the predicted next value: returns (raw, prediction, prob).
    """
    bind = ~state["enc_bound"] & np.isfinite(values)
    if bind.any():
        # bind each field's offset at its first finite value (a leading NaN
        # must not poison the stream's bucket arithmetic forever)
        state["enc_offset"] = np.where(bind, values, state["enc_offset"]).astype(np.float32)
        state["enc_bound"] = state["enc_bound"] | bind
    enc_prev = state.get("enc_prev")  # composite delta fields only
    sdr = encode_record(cfg, values, int(ts_unix), state["enc_offset"],
                        state["enc_resolution"], enc_prev)
    if enc_prev is not None:
        # advance the delta predecessor AFTER encoding (device twin:
        # ops/step._step_impl); NaN gaps keep the pre-gap baseline
        state["enc_prev"] = np.where(
            np.isfinite(values), values, enc_prev).astype(np.float32)
    # TM active cells at t-1: TMOracle rebinds (not mutates) prev_active, so
    # the snapshot needs no copy; only taken when a classifier will read it
    pattern_prev = state["prev_active"].reshape(-1) if classifier is not None else None
    active = sp_compute(state, sdr, cfg.sp, learn)
    raw = tm.compute(active, learn)
    if classifier is None:
        return raw
    from rtap_tpu.models.oracle.classifier import classifier_bucket

    bucket = classifier_bucket(
        float(values[0]), float(state["enc_offset"][0]),
        float(state["enc_resolution"][0]), cfg.classifier.buckets,
    )
    pred, prob = classifier.compute(
        pattern_prev, state["prev_active"].reshape(-1), bucket, float(values[0]), learn
    )
    return raw, pred, prob


@dataclass
class ModelResult:
    """Per-record inference output (the reference's ModelResult.inferences)."""

    raw_score: float  # 1 - |active ∩ predicted| / |active|
    likelihood: float  # rolling-Gaussian tail probability complement
    log_likelihood: float  # NuPIC log-scaled likelihood (the detection score)
    prediction: float | None = None  # predicted next value (SDR classifier)
    prediction_prob: float | None = None  # probability of the argmax bucket


class HTMModel:
    """One HTM anomaly model over one (possibly multivariate) metric stream."""

    def __init__(self, cfg: ModelConfig, seed: int = 0, backend: str = "cpu",
                 _state: dict | None = None):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self.cfg = cfg
        self.backend = backend
        self.seed = seed
        # _state: prebuilt state injection (HTMModel.load) — skips the RNG
        # init whose arrays would be immediately overwritten
        self.state = init_state(cfg, seed) if _state is None else _state
        self.likelihood = AnomalyLikelihood(cfg.likelihood)
        self._classifier = None
        if backend == "cpu":
            self._tm = TMOracle(self.state, cfg.tm)
            if cfg.classifier.enabled:
                from rtap_tpu.models.oracle.classifier import SDRClassifierOracle

                self._classifier = SDRClassifierOracle(self.state, cfg.classifier)
        else:
            from rtap_tpu.ops.step import TpuStepRunner  # deferred: jax import

            self._runner = TpuStepRunner(cfg, self.state)

    def run(self, timestamp: int, value: float | np.ndarray, learn: bool = True) -> ModelResult:
        """Process one record; returns scores. Mirrors model.run({...})."""
        values = np.atleast_1d(np.asarray(value, np.float32))

        if learn and self.cfg.cadence_active:
            # host-side twin of ops/step.py:_tick's schedule (same clock:
            # tm_iter = completed steps, checkpointed, advances under
            # inference; same predicate: cfg.learns_on) so single-stream
            # runs match grouped device runs record-for-record
            it = int(self.state["tm_iter"]) if self.backend == "cpu" else int(
                self._runner.state["tm_iter"]
            )
            learn = bool(self.cfg.learns_on(it))

        pred = prob = None
        if self.backend == "cpu":
            out = oracle_record_step(
                self.cfg, self.state, self._tm, values, int(timestamp), learn,
                classifier=self._classifier,
            )
            raw = out if self._classifier is None else out[0]
            if self._classifier is not None:
                pred, prob = out[1], out[2]
        else:
            # the tpu path performs the offset bind on device
            # (ops/encoders_tpu.bind_offsets) against its own state copy
            out = self._runner.step(values, int(timestamp), learn)
            raw = out if not self.cfg.classifier.enabled else out[0]
            if self.cfg.classifier.enabled:
                pred, prob = out[1], out[2]

        lik, loglik = self.likelihood.update(float(raw))
        return ModelResult(float(raw), lik, loglik, pred, prob)

    # ---- single-model persistence (SURVEY.md C16: the reference's
    # model.save() / ModelFactory.loadFromCheckpoint surface; group-scale
    # checkpoints use service/checkpoint.py's orbax path instead) ----

    def save(self, path: str) -> None:
        """Serialize the FULL model (SDR state, likelihood state machine,
        config, seed) to one .npz; `HTMModel.load` resumes bit-exactly.
        The write is atomic (temp sibling + rename, like the group
        checkpoint path): a crash mid-save can never corrupt an existing
        checkpoint at `path`."""
        import os

        if self.backend == "cpu":
            state = self.state
        else:
            import jax

            state = jax.device_get(self._runner.state)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            np.savez_compressed(
                tmp,
                config_json=np.frombuffer(self.cfg.to_json().encode(), np.uint8),
                seed=np.asarray(self.seed, np.int64),
                **{f"lik_{k}": v for k, v in self.likelihood.state_dict().items()},
                **{f"s_{k}": np.asarray(v) for k, v in state.items()},
            )
            # savez appends .npz when missing — mirror that for the temp name
            if not tmp.endswith(".npz") and os.path.exists(tmp + ".npz"):
                tmp += ".npz"
            os.replace(tmp, path)
        finally:
            # a failed savez may have left either spelling behind (numpy
            # appends .npz to suffix-less names before writing)
            for residue in (tmp, tmp if tmp.endswith(".npz") else tmp + ".npz"):
                if os.path.exists(residue) and os.path.abspath(residue) != os.path.abspath(path):
                    os.unlink(residue)

    @classmethod
    def load(cls, path: str, backend: str = "cpu") -> "HTMModel":
        """Rebuild a model from :meth:`save`. `backend` may differ from the
        saving side (cpu<->tpu resume; the state layout is shared)."""
        with np.load(path) as z:
            cfg = ModelConfig.from_json(bytes(z["config_json"]).decode())
            loaded = {
                k[2:]: z[k]
                for k in z.files
                # fwd_*: a forward index an older build may have stored
                if k.startswith("s_") and not k[2:].startswith("fwd_")
            }
            lik_state = {k[4:]: z[k] for k in z.files if k.startswith("lik_")}
            seed = int(z["seed"])
        model = cls(cfg, seed=seed, backend=backend, _state=loaded)
        model.likelihood.load_state_dict(lik_state)
        return model


def create_model(
    cfg: ModelConfig | None = None,
    backend: str = "cpu",
    seed: int = 0,
    min_val: float = 0.0,
    max_val: float = 100.0,
) -> HTMModel:
    """ModelFactory.create analog. With no explicit config, builds the NAB
    preset sized to the stream's expected [min_val, max_val] range (NAB hands
    detectors the per-file input range the same way)."""
    return HTMModel(cfg or nab_preset(min_val, max_val), seed=seed, backend=backend)


class AnomalyDetector:
    """NAB-detector-shaped wrapper: feed records, get detection scores + alerts.

    The reference's service layer thresholds log-likelihood to raise early
    warnings (SURVEY.md C20, §3.3); `threshold` defaults to the NuPIC-common
    0.5 on the log scale.
    """

    def __init__(
        self,
        cfg: ModelConfig | None = None,
        backend: str = "cpu",
        seed: int = 0,
        min_val: float = 0.0,
        max_val: float = 100.0,
        threshold: float = 0.5,
    ):
        self.model = create_model(cfg, backend, seed, min_val, max_val)
        self.threshold = threshold

    def handle_record(self, timestamp: int, value: float | np.ndarray) -> tuple[float, bool]:
        """-> (detection score in [0,1] (log-likelihood), alert?)."""
        res = self.model.run(timestamp, value)
        return res.log_likelihood, res.log_likelihood >= self.threshold
