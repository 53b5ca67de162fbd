"""`correct` can fail: the configuration's lower-precision control and a
timed path broken underneath both come out not correct, at a size the CPU
holds (the control's readings at the cells' own size are in PERF.md)."""

import numpy as np
import pytest

from tests.benchmark.tiny import failed_numbers, make_root, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload,seconds", [("tiny-replay", 1.0),
                                              ("tiny-live", 3.2)])
def test_sound_run_is_correct_and_u8_control_is_not(root, workload, seconds):
    sound, _ = run(root, workload, 4_100_000_001, seconds)
    assert sound["correct"], sound["compared"]
    control, _ = run(root, workload, 4_100_000_001, seconds, control=True)
    assert not control["correct"]
    assert "perm_max_frac_diff" in failed_numbers(control)


def _altered_score(monkeypatch):
    """An answer altered where it is produced: one raw score of one chunk."""
    from rtap_tpu.service.registry import StreamGroup

    inner = StreamGroup.collect_chunk

    def collect_chunk(self, handle):
        raw, loglik, alerts = inner(self, handle)
        raw = raw.copy()
        raw[-1, :] = np.where(raw[-1, :] > 0.5, 0.0, 1.0)
        return raw, loglik, alerts

    monkeypatch.setattr(StreamGroup, "collect_chunk", collect_chunk)
    return "raw_max_abs_diff"


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: scores flow, nothing learns."""
    import jax
    import jax.numpy as jnp

    from rtap_tpu.service.registry import StreamGroup

    inner = StreamGroup.dispatch_chunk

    def dispatch_chunk(self, values, ts, learn=True):
        kept = jax.tree.map(jnp.copy, self.state)
        handle = inner(self, values, ts, learn=learn)
        self.state = kept
        return handle

    monkeypatch.setattr(StreamGroup, "dispatch_chunk", dispatch_chunk)
    return "perm_max_frac_diff"


@pytest.mark.parametrize("breakage", [_altered_score, _state_unchanged])
@pytest.mark.parametrize("workload,seconds", [("tiny-replay", 1.0),
                                              ("tiny-live", 3.2)])
def test_broken_timed_path_is_not_correct(root, monkeypatch, workload, seconds,
                                          breakage):
    number = breakage(monkeypatch)
    result, _ = run(root, workload, 4_100_000_002, seconds)
    assert not result["correct"]
    assert number in failed_numbers(result)
