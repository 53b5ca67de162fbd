"""Bytes from shapes, and the peaks table."""

import json
import os

import pytest

from benchmark import kernel_bytes_dense as kbd
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


# ---- the dense-pool family: F uniform RDSE fields (kernel_bytes_dense) ----

def _preset(name):
    from rtap_tpu import config

    return {"dense_cluster": config.dense_cluster_preset,
            "node3": lambda: config.node_preset(3),
            "node5": lambda: config.node_preset(5),
            "nab": lambda: config.nab_preset(0.0, 100.0),
            "cluster": config.cluster_preset,
            "composite": config.composite_preset}[name]()


@pytest.mark.parametrize("name,nbytes", [
    ("dense_cluster", 564_245), ("node3", 760_871), ("node5", 957_497),
    ("nab", 281_628_693)])
def test_dense_state_bytes_equal_the_programs_own_count(name, nbytes):
    from rtap_tpu.models.state import state_nbytes

    cfg = _preset(name)
    assert kbd.state_bytes_per_stream(cfg.to_dict()) == nbytes \
        == state_nbytes(cfg)["total"]


@pytest.mark.parametrize("name", ["dense_cluster", "node3", "node5"])
def test_dense_leaves_equal_init_state_leaf_by_leaf(name):
    import numpy as np

    from rtap_tpu.models.state import init_state

    cfg = _preset(name)
    state = init_state(cfg, 0, include_fwd=False)
    leaves = kbd.leaf_bytes(cfg.to_dict())
    assert set(kbd.STATE_LEAVES) == set(state)
    for k in kbd.STATE_LEAVES:
        assert leaves[k] == np.asarray(state[k]).nbytes, k
    assert leaves["sdr"] == cfg.input_size


@pytest.mark.parametrize("scope,nbytes", [
    ("rtap.sp.overlap", 296_320), ("rtap.sp.learn", 497_288),
    ("rtap.tm", 534_784)])
def test_dense_kernel_bytes_of_three_fields(scope, nbytes):
    model = _preset("node3").to_dict()
    assert kbd.kernel_bytes_per_stream(scope, model) == nbytes
    # the TM does not know how many fields fed the SP; the SP's input does
    one = _preset("dense_cluster").to_dict()
    same = scope == "rtap.tm"
    assert (kbd.kernel_bytes_per_stream(scope, one) == nbytes) == same
    assert kbd.kernel_floor_seconds(scope, model, 1024, "TPU v5 lite") == \
        pytest.approx(nbytes * 1024 / 819e9)


@pytest.mark.parametrize("name,change", [
    ("cluster", {}), ("composite", {}),
    ("node3", {"n_fields": 0}), ("node3", {"classifier": {"enabled": True}}),
    ("node3", {"scalar": {"any": "scalar encoder"}})])
def test_dense_byte_table_refuses_what_it_does_not_count(name, change):
    model = {**_preset(name).to_dict(), **change}
    with pytest.raises(ValueError, match="n_fields >= 1 uniform RDSE fields "
                                         "only: not a sparse pool"):
        kbd.leaf_bytes(model)
