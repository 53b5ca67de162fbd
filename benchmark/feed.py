"""The seeded metric feed every traffic kind draws from.

`make_sine_feed` is a copy of rtap_tpu/utils/measure.py:make_sine_feed (PERF.md
lists the original under Open questions): a diurnal sine plus Gaussian noise
per stream, Philox-keyed, so the same key gives the same values anywhere —
in the measuring process, in the generator process and in the reference."""

from __future__ import annotations

import numpy as np


def make_sine_feed(G: int, chunk_ticks: int, key: tuple[int, int], t0: int = 0,
                   phase: np.ndarray | None = None):
    """-> (values [T, G] f32, ts [T, G] i64, phase [G]); pass `phase` back in
    to generate consecutive chunks of the same streams."""
    rng = np.random.Generator(np.random.Philox(key=key))
    if phase is None:
        phase = rng.integers(0, 86400, G)
    t_idx = t0 + np.arange(chunk_ticks)[:, None]
    base = 35.0 + 20.0 * np.sin(2 * np.pi * (t_idx + phase[None, :]) / 86400.0)
    vals = (base + rng.normal(0, 3.0, (chunk_ticks, G))).astype(np.float32)
    ts = (1_700_000_000 + t_idx + np.zeros((1, G))).astype(np.int64)
    return vals, ts, phase


def seed_key(seed: int, lane: int) -> tuple[int, int]:
    """A Philox key from the run's --seed (any whole number; the driver's are
    above 2**31) and a lane (which group / slot / purpose draws)."""
    return (int(seed) % (1 << 63), int(lane))


def sample_streams(seed: int, n_streams: int, n_sample: int) -> np.ndarray:
    """The seeded sample of stream indices whose scores and state `correct`
    compares, ascending; always holds the first and the last stream."""
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x5A3)))
    n = min(n_sample, n_streams)
    picks = set(rng.choice(n_streams, size=n, replace=False).tolist())
    picks |= {0, n_streams - 1}
    return np.array(sorted(picks), np.int64)


def stream_ids(n: int) -> list[str]:
    """The ids the benchmark's streams carry on the wire and in the
    registry: four metrics per node."""
    return [f"node{i // 4:05d}.m{i % 4}" for i in range(n)]


def live_rows(seed: int, n_streams: int, n_slots: int, spread_s: float,
              quantum_s: float):
    """The offered set of a live run, a pure function of its arguments ->
    (values [n_slots, n_streams] f32, due offset phi [n_streams] seconds,
    send offset [n_streams] seconds). Stream i's row of slot k is due at
    E + k * cadence + phi[i]; the generator puts it on the wire at the next
    multiple of `quantum_s`, never before it is due."""
    values, _, _ = make_sine_feed(n_streams, n_slots, seed_key(seed, 0x11FE))
    # every seed offers the same set of due offsets — an even grid over the
    # spread — dealt to the streams in a seeded order, so that the seed
    # changes who is due when and never the schedule's own distribution
    rng = np.random.Generator(np.random.Philox(key=seed_key(seed, 0x0FF5E7)))
    grid = (np.arange(n_streams) + 0.5) / n_streams * spread_s
    phi = grid[rng.permutation(n_streams)]
    send = np.ceil(phi / quantum_s) * quantum_s
    return values, phi, send
