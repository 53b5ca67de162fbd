"""The live kind's accounting on the CPU at a tiny size: the offered rows
are a constant of the cell and a sound run fails none of them; a poll stalled
past the guard costs the held traffic (the cells') latency and no row, and
fails exactly the rows it let be overwritten where nothing holds them."""

import numpy as np
import pytest

from benchmark.feed import live_rows
from tests.benchmark.tiny import TINY_LIVE, make_root, run

SECONDS = 4.6  # 4 slots of 1.0 s
N, S = 4, 16


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def test_attempted_is_a_constant_and_nothing_fails(root):
    a, rec_a = run(root, "tiny-live", 4_000_000_001, SECONDS)
    b, rec_b = run(root, "tiny-live", 4_000_000_002, SECONDS)
    assert a["attempted"] == b["attempted"] == N * S
    assert a["failed"] == b["failed"] == 0
    assert a["correct"] and b["correct"]
    for rec in (rec_a, rec_b):
        # every offered row was scored by the tick after its slot, exactly
        # once; nothing exists before slot 0 or after slot N-1
        assert (rec["scored_tick"] == np.arange(1, N + 1)[:, None]).all()
        assert rec["generator"]["rows_sent"] == N * S
        assert rec["ticks_run"] == N + 1  # priming + N, no drain needed
        report = rec["generator"]
        assert min(report["first_send"]) >= rec["E"]
        assert max(report["last_send"]) <= rec["E"] + N * TINY_LIVE["cadence_s"]
        assert rec["guard_before_s"] > 0 and rec["guard_after_s"] > 0
    assert set(a["metrics"]) == {"score_p50_ms",
                                 "peak_bytes_per_stream", "setup_s"}


J = 2  # the tick that should snapshot slot 1 snapshots late ...
STALL = TINY_LIVE["guard_s"] + 0.27  # ... landing between the two send batches


def test_a_stalled_poll_holds_rows_back_and_loses_none(root):
    res, rec = run(root, "tiny-live", 4_000_000_004, SECONDS,
                   hooks={"stall": {J: STALL}})
    assert res["attempted"] == N * S and res["failed"] == 0 and res["correct"]
    assert (rec["scored_tick"] == np.arange(1, N + 1)[:, None]).all()
    report = rec["generator"]
    # slot J's first batch was due before the late snapshot and waited for it
    assert report["batches_held"] >= 1
    assert report["first_send"][J] > rec["snap_t"][J]
    # ... and its wait is counted: lateness runs from the due time
    assert report["late_ms_max"] > (STALL - TINY_LIVE["guard_s"]
                                    - TINY_LIVE["send_quantum_s"]) * 1e3
    # the stall sits before the snapshot: the rows' delay from their due time
    # carries it, their snapshot -> emitted latency (the cell's end-to-end
    # number) does not
    assert (rec["end_to_end"]["score_p50_ms"] < STALL * 1e3
            < rec["row_latency_ms"]["detect_p95"])


def test_unheld_a_stalled_poll_fails_exactly_the_overwritten_rows(root):
    j, stall = J, STALL
    seed = 4_000_000_003
    res, rec = run(root, "tiny-live-unheld", seed, SECONDS,
                   hooks={"stall": {j: stall}})
    _values, _phi, send = live_rows(seed, S, N, TINY_LIVE["phase_spread_s"],
                                    TINY_LIVE["send_quantum_s"])
    batch_of = np.unique(send, return_inverse=True)[1]
    sent_at = np.array(rec["generator"]["sent_at"])  # [slot, batch]
    # slot j's rows already on the wire when tick j snapshotted overwrote
    # slot j-1's rows of the same streams
    early = sent_at[j, batch_of] < rec["snap_t"][j]
    assert 0 < early.sum() < S
    lost = rec["scored_tick"] < 0
    expected = np.zeros((N, S), bool)
    expected[j - 1, early] = True
    assert (lost == expected).all()
    assert res["attempted"] == N * S and res["failed"] == int(early.sum())
    # the early rows were scored by tick j itself, the others on time
    assert (rec["scored_tick"][j, early] == j).all()
    assert (rec["scored_tick"][j, ~early] == j + 1).all()
