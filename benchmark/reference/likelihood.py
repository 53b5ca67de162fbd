"""The plain reference of what follows the raw score: the anomaly likelihood
in its streaming mode, and the alert rule over it.

The benchmark's own copy of the semantics of
rtap_tpu/models/oracle/likelihood.py (NuPIC's anomaly_likelihood.py with the
historic window replaced by exponentially decayed moments), one stream at a
time in float64, importing nothing of the program. A raw score is averaged
over the last `averaging_window` ticks; the average's decayed mean and
deviation are kept from the first tick on; once `learning_period +
estimation_samples` ticks have been seen the likelihood is 1 - Q((average -
mean) / deviation), reported on NuPIC's log scale; before that it is the
noncommittal 0.5. A stream alerts at a tick iff its log-likelihood has been at
or above `threshold` for `debounce` ticks in a row, that tick included."""

from __future__ import annotations

import math
from collections import deque

import numpy as np

_LOG_DENOM = math.log(1e-10)


def probation(cfg: dict) -> int:
    """Ticks before the likelihood says anything (the configuration file's
    `model.likelihood`)."""
    return int(cfg["learning_period"]) + int(cfg["estimation_samples"])


def log_likelihoods(raw, cfg: dict) -> np.ndarray:
    """One stream's raw scores, in order from the making of its model ->
    its log-likelihood at every tick [T] f64."""
    if cfg["mode"] != "streaming":
        raise ValueError(f"the reference follows the streaming likelihood; "
                         f"the configuration states {cfg['mode']!r}")
    decay, wait = float(cfg["streaming_decay"]), probation(cfg)
    recent: deque = deque(maxlen=int(cfg["averaging_window"]))
    s0 = s1 = s2 = 0.0
    out = np.empty(len(raw), np.float64)
    for t, score in enumerate(np.asarray(raw, np.float64)):
        recent.append(float(score))
        avg = sum(recent) / len(recent)
        s0 = decay * s0 + 1.0
        s1 = decay * s1 + avg
        s2 = decay * s2 + avg * avg
        mean = s1 / s0
        std = max(math.sqrt(max(s2 / s0 - mean * mean, 0.0)), 1e-6)
        if t + 1 < wait:
            lik = 0.5
        else:
            lik = 1.0 - 0.5 * math.erfc((avg - mean) / std / math.sqrt(2.0))
        out[t] = math.log(1.0000000001 - lik) / _LOG_DENOM
    return out


def alerts(loglik, threshold: float, debounce: int) -> np.ndarray:
    """The alert rule over one stream's log-likelihoods -> [T] bool."""
    out = np.zeros(len(loglik), bool)
    run = 0
    for t, x in enumerate(loglik):
        run = run + 1 if x >= threshold else 0
        out[t] = run >= debounce
    return out


def judged_alerts(loglik, threshold: float, debounce: int, eps: float):
    """-> (due [T] bool, judged [T] bool): the ticks at which an alert line
    is due, and the ticks at which the rule can be held against a program
    whose log-likelihood may differ from `loglik` by up to `eps`. The rule is
    monotone in the threshold, so a tick is due for certain where it alerts
    at `threshold + eps`, certainly not due where it does not at `threshold -
    eps`, and not judged in between."""
    sure = alerts(loglik, threshold + eps, debounce)
    maybe = alerts(loglik, threshold - eps, debounce)
    return sure, sure | ~maybe
