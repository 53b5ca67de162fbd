"""Bytes from shapes, and the peaks table."""

import json
import os

import pytest

from benchmark.roofline import peaks, state_bytes_per_stream, step_floor_seconds

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                       "configs")


def model(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name,nbytes", [("cluster-256", 302_101),
                                         ("cluster-32", 37_781)])
def test_state_bytes_from_shapes(name, nbytes):
    # ROADMAP "State of the evidence": the presets' own bytes per stream
    assert state_bytes_per_stream(model(name)) == nbytes


def test_shape_bytes_equal_the_reference_arrays():
    import numpy as np

    from benchmark.reference.config import ModelConfig
    from benchmark.reference.state import init_state

    for name in ("cluster-256", "cluster-32"):
        st = init_state(ModelConfig.from_dict(model(name)), 0)
        assert sum(np.asarray(v).nbytes for v in st.values()) == \
            state_bytes_per_stream(model(name))


def test_u8_control_is_smaller():
    m = model("cluster-256")
    m["sp"]["perm_bits"] = m["tm"]["perm_bits"] = 8
    assert state_bytes_per_stream(m) == 236_565  # config.py:cluster_preset doc


def test_floor_and_peaks():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    assert step_floor_seconds(model("cluster-256"), 1024, "TPU v5 lite") == \
        pytest.approx(2 * 302_101 * 1024 / 819e9)
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("_source")
