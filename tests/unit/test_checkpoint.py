"""Checkpoint round-trip: save mid-stream, resume, outputs stay bit-identical
to the uninterrupted run (SURVEY.md §4 item 3 — the serialization test
pattern NuPIC uses for its Cap'n Proto save/resume)."""

import numpy as np
import pytest

from rtap_tpu.config import cluster_preset
from rtap_tpu.service.checkpoint import load_group, save_group
from rtap_tpu.service.registry import StreamGroup


def _vals(n, g, seed):
    rng = np.random.Generator(np.random.Philox(key=(seed, 11)))
    v = (40 + 8 * rng.random((n, g))).astype(np.float32)
    v[int(n * 0.7), :] += 50
    return v


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_group_checkpoint_roundtrip(backend, tmp_path):
    cfg = cluster_preset()
    ids = [f"s{i}" for i in range(3)]
    n, cut = 160, 80
    vals = _vals(n, 3, seed=1)

    ref = StreamGroup(cfg, ids, backend=backend)
    for i in range(cut):
        ref.tick(vals[i], 1_700_000_000 + i)
    save_group(ref, tmp_path / "grp0")

    resumed = load_group(tmp_path / "grp0")
    assert resumed.stream_ids == ids and resumed.ticks == cut
    for i in range(cut, n):
        r_ref = ref.tick(vals[i], 1_700_000_000 + i)
        r_res = resumed.tick(vals[i], 1_700_000_000 + i)
        np.testing.assert_array_equal(r_ref.raw, r_res.raw, err_msg=f"tick {i}")
        np.testing.assert_array_equal(
            r_ref.log_likelihood, r_res.log_likelihood, err_msg=f"tick {i}"
        )
        np.testing.assert_array_equal(r_ref.alerts, r_res.alerts)


def test_loaders_drop_a_forward_index_an_older_build_stored(tmp_path):
    """Builds before PR 29 could carry fwd_* leaves (a forward synapse index)
    in a state; a stored tree that has them loads without them, as a group
    and as a single model, and steps."""
    import jax.numpy as jnp

    from rtap_tpu.models.htm_model import HTMModel

    cfg = cluster_preset()
    grp = StreamGroup(cfg, ["a", "b"], backend="tpu")
    grp.tick(np.float32([41.0, 43.0]), 1_700_000_000)
    grp.state = {**grp.state, "fwd_of": jnp.zeros(2, jnp.int32),
                 "fwd_slots": jnp.full((2, cfg.num_cells, 4), -1, jnp.int32)}
    save_group(grp, tmp_path / "grp0")
    back = load_group(tmp_path / "grp0")
    assert not [k for k in back.state if k.startswith("fwd_")]
    back.tick(np.float32([42.0, 44.0]), 1_700_000_001)

    m = HTMModel(cfg, seed=4, backend="cpu")
    m.run(1_700_000_000, 41.0)
    m.save(str(tmp_path / "m.npz"))
    with np.load(tmp_path / "m.npz") as z:
        stored = {k: z[k] for k in z.files}
    np.savez(tmp_path / "old.npz", **stored, s_fwd_of=np.int32(0),
             s_fwd_slots=np.full((cfg.num_cells, 4), -1, np.int32))
    old = HTMModel.load(str(tmp_path / "old.npz"), backend="tpu")
    assert not [k for k in old._runner.state if k.startswith("fwd_")]
    old.run(1_700_000_001, 42.0)


def test_checkpoint_preserves_config_and_threshold(tmp_path):
    cfg = cluster_preset()
    grp = StreamGroup(cfg, ["a", "b"], backend="cpu", threshold=0.37)
    grp.tick(np.array([1.0, 2.0], np.float32), 1_700_000_000)
    save_group(grp, tmp_path / "g")
    back = load_group(tmp_path / "g")
    assert back.threshold == 0.37
    assert back.cfg == cfg
    assert back.backend == "cpu"


def test_checkpoint_overwrite_atomic(tmp_path):
    """Re-saving to an existing path swaps directories whole: the new state is
    readable and no temp/old residue remains."""
    cfg = cluster_preset()
    grp = StreamGroup(cfg, ["a", "b"], backend="cpu")
    grp.tick(np.array([1.0, 2.0], np.float32), 1_700_000_000)
    save_group(grp, tmp_path / "g")
    grp.tick(np.array([3.0, 4.0], np.float32), 1_700_000_001)
    save_group(grp, tmp_path / "g")  # overwrite
    back = load_group(tmp_path / "g")
    assert back.ticks == 2
    residue = [p.name for p in tmp_path.iterdir() if p.name != "g"]
    assert residue == [], residue


def test_config_validation_rejects_small_col_cap():
    from rtap_tpu.config import ModelConfig, SPConfig, TMConfig

    with pytest.raises(ValueError, match="col_cap"):
        ModelConfig(sp=SPConfig(num_active_columns=50),
                    tm=TMConfig(cells_per_column=32, col_cap=10))
    ModelConfig()  # defaults must validate


def test_from_dict_drops_retired_fields_and_clamps_col_cap():
    from rtap_tpu.config import ModelConfig, SPConfig

    old = ModelConfig(sp=SPConfig(num_active_columns=40)).to_dict()
    old["tm"]["active_cap"] = 512  # retired field from an old serialization
    old["tm"]["winner_cap"] = 192
    old["tm"]["col_cap"] = 8  # pre-col_cap checkpoint migrated too low
    cfg = ModelConfig.from_dict(old)
    assert cfg.tm.col_cap == 40


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_checkpoint_roundtrip_with_classifier(backend, tmp_path):
    """Classifier weights/actual-values resume with the group: predictions
    after resume match the uninterrupted run exactly."""
    from tests.unit.test_classifier import _cfg, _periodic_values

    cfg = _cfg()
    ids = ["a", "b"]
    vals = _periodic_values(120)
    ref = StreamGroup(cfg, ids, backend=backend)
    for i in range(60):
        ref.tick(np.array([vals[i], vals[i] + 1], np.float32), 1_700_000_000 + i)
    save_group(ref, tmp_path / "g")
    resumed = load_group(tmp_path / "g")
    for i in range(60, 120):
        v = np.array([vals[i], vals[i] + 1], np.float32)
        r_ref = ref.tick(v, 1_700_000_000 + i)
        r_res = resumed.tick(v, 1_700_000_000 + i)
        np.testing.assert_array_equal(r_ref.raw, r_res.raw, err_msg=f"tick {i}")
        np.testing.assert_array_equal(r_ref.prediction, r_res.prediction, err_msg=f"tick {i}")


class TestDenseToSparseMigration:
    """ISSUE 18: a COMMITTED dense-layout checkpoint restores into the
    sparse build (``load_group(..., sparsify=True)``) and continues
    bit-identically to the dense run recorded at fixture-creation time
    (scripts/make_migration_fixture.py). The re-layout is lossless: every
    synapse keeps its exact permanence, so scores can never drift."""

    FIXTURE = "tests/fixtures/migration"

    def _fixture(self):
        import pathlib

        root = pathlib.Path(__file__).resolve().parents[2] / self.FIXTURE
        exp = np.load(root / "expected.npz")
        return root / "dense_ckpt", exp

    def test_committed_dense_checkpoint_restores_sparse_bit_identical(self):
        ckpt, exp = self._fixture()
        grp = load_group(ckpt, sparsify=True)
        # the resumed group IS the sparse build: layout flipped, the
        # migration's exact pool width pinned, dense mask gone
        assert grp.cfg.sp.sparse_pool
        assert grp.cfg.sp.pool_members == grp.cfg.sp_members > 0
        assert "members" in grp.state and "potential" not in grp.state
        warm = int(exp["warm_ticks"])
        vals = exp["vals"]
        for j in range(exp["raw"].shape[0]):
            r = grp.tick(vals[warm + j], 1_700_000_000 + warm + j)
            np.testing.assert_array_equal(r.raw, exp["raw"][j], err_msg=f"tick {j}")
            np.testing.assert_array_equal(
                r.log_likelihood, exp["log_likelihood"][j], err_msg=f"tick {j}")

    def test_sparsify_noop_on_already_sparse_checkpoint(self, tmp_path):
        cfg = cluster_preset()  # sparse layout since ISSUE 18
        grp = StreamGroup(cfg, ["a", "b"], backend="tpu")
        grp.tick(np.array([1.0, 2.0], np.float32), 1_700_000_000)
        save_group(grp, tmp_path / "g")
        back = load_group(tmp_path / "g", sparsify=True)
        assert back.cfg == cfg  # untouched: no pool_members pin, same layout


class TestSingleModelSaveLoad:
    """HTMModel.save/load (SURVEY.md C16 model.save surface): resume is
    bit-exact vs an uninterrupted run, across backends and domains."""

    def _vals(self, n=220):
        import numpy as np

        t = np.arange(n)
        v = (50 + 20 * np.sin(2 * np.pi * t / 40.0)
             + np.random.default_rng(8).normal(0, 2, n)).astype(np.float32)
        v[int(0.77 * n)] += 35
        return v

    @pytest.mark.parametrize("perm_bits", [0, 16])
    def test_roundtrip_bit_exact(self, tmp_path, perm_bits):
        import dataclasses

        import numpy as np

        from rtap_tpu.models.htm_model import HTMModel

        base = cluster_preset(perm_bits=perm_bits)
        cfg = dataclasses.replace(
            base, likelihood=dataclasses.replace(
                base.likelihood, learning_period=60, estimation_samples=30)
        )
        vals = self._vals()
        full = HTMModel(cfg, seed=4, backend="cpu")
        ref = [full.run(1_700_000_000 + i, float(vals[i])) for i in range(220)]

        m = HTMModel(cfg, seed=4, backend="cpu")
        for i in range(150):
            m.run(1_700_000_000 + i, float(vals[i]))
        p = tmp_path / "model.npz"
        m.save(str(p))
        resumed = HTMModel.load(str(p))
        assert resumed.cfg == cfg
        out = [resumed.run(1_700_000_000 + i, float(vals[i])) for i in range(150, 220)]
        for a, b in zip(out, ref[150:]):
            assert a.raw_score == b.raw_score
            assert a.log_likelihood == b.log_likelihood
        # saved state untouched by the resumed run's mutation
        with np.load(p) as z:
            assert int(z["lik_records"]) == 150

    def test_cpu_save_tpu_resume(self, tmp_path):
        from rtap_tpu.models.htm_model import HTMModel

        cfg = cluster_preset()
        vals = self._vals(120)
        m = HTMModel(cfg, seed=4, backend="cpu")
        for i in range(80):
            m.run(1_700_000_000 + i, float(vals[i]))
        p = tmp_path / "model.npz"
        m.save(str(p))
        cpu = HTMModel.load(str(p), backend="cpu")
        tpu = HTMModel.load(str(p), backend="tpu")
        for i in range(80, 120):
            a = cpu.run(1_700_000_000 + i, float(vals[i]))
            b = tpu.run(1_700_000_000 + i, float(vals[i]))
            assert a.raw_score == b.raw_score, i
