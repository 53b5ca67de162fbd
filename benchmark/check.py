"""How `correct` is decided: the timed path's own output against the plain
reference (benchmark/reference), number by number, each beside its limit.

A traffic kind hands over, for a seeded sample of streams, exactly what the
window fed each stream and what the program served for it; the reference
follows the same feed from the same seed, outside the timed window and
without touching the device. PERF.md gives the readings every limit was set
from."""

from __future__ import annotations

import time

import numpy as np

from benchmark.reference.config import ModelConfig
from benchmark.reference.model import ReferenceStream

#: leaves whose every element is a permanence (compared as a fraction of
#: full scale, so a program in another quantum is still comparable)
PERM_LEAVES = ("perm", "syn_perm")
_FULL_SCALE = {np.dtype(np.uint8): 255.0, np.dtype(np.uint16): 65535.0,
               np.dtype(np.float32): 1.0}


def perm_fraction(arr: np.ndarray) -> np.ndarray:
    arr = np.asarray(arr)
    return arr.astype(np.float64) / _FULL_SCALE[arr.dtype]


def describe(n: dict) -> str:
    """One compared number beside its limit, as every run prints it."""
    return (f"{n['name']} {n['value']:.6g} (limit {n['limit']:g}) "
            f"{'ok' if n['ok'] else 'FAILED'}")


def compare(config: dict, sample: list[dict], tm_overflow: int,
            rows_misrouted: int, say=print
            ) -> tuple[bool, list[dict], float, int]:
    """-> (correct, the numbers compared, seconds the reference took, the
    most ticks any sampled stream was followed over).

    `sample`: one dict per sampled stream — ``seed`` (its model state's),
    ``ts`` and ``values`` (the ticks the kind has the reference follow, from
    the making of the stream's state on and in order, each row as the
    program was fed it: a scalar, or the ``[n_fields]`` vector of a
    multi-field model; NaN = missing sample), ``raw`` (the score the program
    served for each of those ticks) and the stream's rows of PERM_LEAVES as
    the device held them after the last of those ticks."""
    t0 = time.perf_counter()
    ref_cfg = ModelConfig.from_dict(config["model"])
    raw_gap = perm_gap = 0.0
    ticks = most = 0
    for s in sample:
        if not (len(s["ts"]) == len(s["values"]) == len(s["raw"])):
            raise ValueError(f"stream {s['stream']}: fed {len(s['values'])} "
                             f"ticks, served {len(s['raw'])}")
        row = np.shape(s["values"])[1:]
        if row != (ref_cfg.n_fields,) and not (row == () and ref_cfg.n_fields == 1):
            raise ValueError(
                f"stream {s['stream']}: fed rows of shape {row}, the "
                f"configuration's model takes {ref_cfg.n_fields} field(s) a row")
        ref = ReferenceStream(ref_cfg, s["seed"])
        ref_raw = np.array([ref.run(int(t), v)
                            for t, v in zip(s["ts"], s["values"])], np.float32)
        served = np.asarray(s["raw"], np.float32)
        gap = np.abs(ref_raw - served)
        # a NaN score never equals the reference's
        raw_gap = max(raw_gap, float(np.where(np.isfinite(gap), gap, np.inf)
                                     .max(initial=0.0)))
        for leaf in PERM_LEAVES:
            perm_gap = max(perm_gap, float(np.abs(
                perm_fraction(s[leaf]) - perm_fraction(ref.state[leaf])).max()))
        ticks += len(served)
        most = max(most, len(served))
    prec = config["precision"]
    numbers = [
        {"name": "raw_max_abs_diff", "value": raw_gap,
         "limit": prec["raw_tolerance"]},
        {"name": "perm_max_frac_diff", "value": perm_gap,
         "limit": prec["perm_tolerance"]},
        {"name": "tm_overflow", "value": tm_overflow, "limit": 0},
        {"name": "rows_misrouted", "value": rows_misrouted, "limit": 0},
    ]
    for n in numbers:
        n["ok"] = bool(n["value"] <= n["limit"])
    if not ticks:
        raise ValueError("no stream-tick to compare: the run served nothing "
                         "for the sampled streams")
    dt = time.perf_counter() - t0
    say(f"[correct] {len(sample)} sampled streams x their "
        f"{ticks // max(1, len(sample))} ticks against benchmark/reference "
        f"in {dt:.2f}s: " + "; ".join(map(describe, numbers)))
    return all(n["ok"] for n in numbers), numbers, dt, most
