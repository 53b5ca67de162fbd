"""Sharded execution on the 8-virtual-device CPU mesh (SURVEY.md §4 item 6).

Validates the multi-chip design without hardware: stream-axis sharding
produces bit-identical scores to single-device execution, the compiled hot
loop contains no collectives (streams are independent by construction), and
the service layer runs transparently over a mesh.
"""

import numpy as np
import pytest

import jax

from rtap_tpu.config import cluster_preset
from rtap_tpu.parallel import make_stream_mesh, shard_state, stream_sharding
from rtap_tpu.service.registry import StreamGroup

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-virtual-device test mesh"
)


def _vals(n, g, seed=4):
    rng = np.random.Generator(np.random.Philox(key=(seed, 21)))
    v = (40 + 8 * rng.random((n, g))).astype(np.float32)
    v[n // 2, :: 3] += 45
    return v


def test_sharded_matches_single_device():
    cfg = cluster_preset()
    G, T = 16, 40
    ids = [f"s{i}" for i in range(G)]
    mesh = make_stream_mesh(8)
    plain = StreamGroup(cfg, ids, backend="tpu")
    sharded = StreamGroup(cfg, ids, backend="tpu", mesh=mesh)
    vals = _vals(T, G)
    ts = (1_700_000_000 + np.arange(T)[:, None] + np.zeros((1, G))).astype(np.int64)
    r_p, ll_p, _ = plain.run_chunk(vals, ts)
    r_s, ll_s, _ = sharded.run_chunk(vals, ts)
    np.testing.assert_array_equal(r_p, r_s)
    np.testing.assert_array_equal(ll_p, ll_s)
    # state stays sharded across steps (donation preserves sharding)
    leaf = sharded.state["perm"]
    assert len(leaf.sharding.device_set) == 8


def test_sharded_cadence_matches_single_device():
    """Learning cadence under shard_map: the schedule cond reads each
    shard's own tm_iter slice (lockstep across shards by construction), so
    sharded and single-device execution must stay bit-identical with
    learn_every set. Pins the r4 cadence feature on the production
    multi-chip path."""
    import dataclasses

    cfg = dataclasses.replace(cluster_preset(), learn_every=3, learn_full_until=10)
    G, T = 16, 30
    ids = [f"s{i}" for i in range(G)]
    mesh = make_stream_mesh(8)
    plain = StreamGroup(cfg, ids, backend="tpu")
    sharded = StreamGroup(cfg, ids, backend="tpu", mesh=mesh)
    vals = _vals(T, G, seed=9)
    ts = (1_700_000_000 + np.arange(T)[:, None] + np.zeros((1, G))).astype(np.int64)
    r_p, ll_p, _ = plain.run_chunk(vals, ts)
    r_s, ll_s, _ = sharded.run_chunk(vals, ts)
    np.testing.assert_array_equal(r_p, r_s)
    np.testing.assert_array_equal(ll_p, ll_s)


def test_hot_loop_is_collective_free():
    """No cross-chip communication in the compiled sharded step — the whole
    point of the stream-axis design (SURVEY.md §2.3). Plain jit over sharded
    inputs does NOT have this property (the partitioner all-gathers the TopK
    batch), which is why the service layer uses shard_map."""
    from rtap_tpu.models.state import init_state
    from rtap_tpu.ops.step import _sharded_chunk_fn, replicate_state

    cfg = cluster_preset()
    G, T = 16, 4
    mesh = make_stream_mesh(8)
    state = shard_state(replicate_state(init_state(cfg, 0), G), mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    vals = jax.device_put(np.zeros((T, G, 1), np.float32),
                          NamedSharding(mesh, P(None, "streams", None)))
    ts = jax.device_put(np.zeros((T, G), np.int32),
                        NamedSharding(mesh, P(None, "streams")))
    state_ranks = tuple(sorted((k, max(np.ndim(v), 1)) for k, v in state.items()))
    fn = _sharded_chunk_fn(cfg, mesh, True, state_ranks)
    txt = fn.lower(state, vals, ts).compile().as_text()
    for coll in ("all-reduce", "all-gather", "collective-permute", "all-to-all", "reduce-scatter"):
        assert coll not in txt, f"unexpected collective {coll} in sharded hot loop"


def test_sharded_matches_single_device_flat_layout():
    """The narrow-row form's chunk-boundary adapters (flat pools) run INSIDE
    shard_map (per-shard reshapes) — sharded must equal single-device."""
    import rtap_tpu.ops.tm_tpu as tm_tpu

    cfg = cluster_preset()
    assert not tm_tpu.wide_rows(cfg.tm)
    G, T = 16, 24
    ids = [f"f{i}" for i in range(G)]
    vals = _vals(T, G)
    ts = (1_700_000_000 + np.arange(T)[:, None] + np.zeros((1, G))).astype(np.int64)
    plain = StreamGroup(cfg, ids, backend="tpu")
    r_p, ll_p, _ = plain.run_chunk(vals, ts)
    sharded = StreamGroup(cfg, ids, backend="tpu", mesh=make_stream_mesh(8))
    r_s, ll_s, _ = sharded.run_chunk(vals, ts)
    np.testing.assert_array_equal(r_p, r_s)
    np.testing.assert_array_equal(ll_p, ll_s)


def test_registry_over_mesh():
    cfg = cluster_preset()
    mesh = make_stream_mesh(8)
    from rtap_tpu.service.registry import StreamGroupRegistry

    reg = StreamGroupRegistry(cfg, group_size=8, backend="tpu", mesh=mesh)
    for i in range(11):  # second group padded 3 live + 5 pad
        reg.add_stream(f"n{i}")
    reg.finalize()
    assert len(reg.groups) == 2
    rng = np.random.Generator(np.random.Philox(key=(9, 2)))
    for grp in reg.groups:
        res = grp.tick((40 + rng.random(grp.G)).astype(np.float32), 1_700_000_000)
        assert np.isfinite(res.raw).all()


def test_shard_state_rejects_indivisible():
    cfg = cluster_preset()
    from rtap_tpu.models.state import init_state
    from rtap_tpu.ops.step import replicate_state

    mesh = make_stream_mesh(8)
    with pytest.raises(ValueError, match="not divisible"):
        shard_state(replicate_state(init_state(cfg, 0), 12), mesh)


def test_dynamic_claim_on_meshed_group():
    """Dynamic slot claims work on sharded groups (elastic fleets on the
    multi-chip path): the claimed slot's row reset is bit-identical to the
    single-device claim, sharding survives the donated update, and scoring
    continues bit-equal across the mesh boundary."""
    cfg = cluster_preset()
    G, T = 16, 12
    ids = [f"s{i}" for i in range(G - 2)] + ["__pad0", "__pad1"]
    mesh = make_stream_mesh(8)
    plain = StreamGroup(cfg, ids, backend="tpu")
    sharded = StreamGroup(cfg, ids, backend="tpu", mesh=mesh)
    vals = _vals(T, G, seed=13)
    ts = (1_700_000_000 + np.arange(T)[:, None] + np.zeros((1, G))).astype(np.int64)
    plain.run_chunk(vals, ts)
    sharded.run_chunk(vals, ts)

    sp = plain.claim_slot("late")
    ss = sharded.claim_slot("late")
    assert sp == ss == G - 2
    for key in plain.state:
        np.testing.assert_array_equal(
            np.asarray(plain.state[key]), np.asarray(sharded.state[key]),
            err_msg=key)
    # sharding preserved through the donated row update
    assert len(sharded.state["perm"].sharding.device_set) == 8

    vals2 = _vals(T, G, seed=14)
    ts2 = ts + T
    r_p, ll_p, _ = plain.run_chunk(vals2, ts2)
    r_s, ll_s, _ = sharded.run_chunk(vals2, ts2)
    np.testing.assert_array_equal(r_p, r_s)
    np.testing.assert_array_equal(ll_p, ll_s)


def test_live_serving_stack_over_mesh_bitexact():
    """The full round-5 serving stack (stagger_learn + micro_chunk +
    chunk_stagger + threaded dispatch, live_loop) over a MESHED registry
    must produce bit-identical output to the same stack unmeshed — the
    100k-per-chip serving shape composes with the v5e-8 scale-out axis
    unchanged (SURVEY.md §2.3: shard, then serve exactly the same way)."""
    import dataclasses
    import tempfile

    from rtap_tpu.config import LikelihoodConfig
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    # a 15-tick fresh model cannot alert discriminatively (the TM knows
    # nothing yet); a floor threshold makes every emitted log-likelihood
    # cross it, so the alert file carries REAL per-stream values through
    # the full emission path — the comparison is content-bearing, not two
    # empty files
    cfg = dataclasses.replace(
        cluster_preset(), learn_every=2,
        likelihood=LikelihoodConfig(mode="streaming", learning_period=4,
                                    estimation_samples=4,
                                    averaging_window=3))
    n, gsize, ticks = 12, 8, 15

    def _feed(k):
        rng = np.random.Generator(np.random.Philox(key=(31, k)))
        v = (40 + 6 * rng.random(n)).astype(np.float32)
        if k >= 9:
            v[::3] += 70.0
        return v, 1_700_000_000 + k

    out = {}
    for mode in ("plain", "mesh"):
        mesh = make_stream_mesh(8) if mode == "mesh" else None
        reg = StreamGroupRegistry(cfg, group_size=gsize, backend="tpu",
                                  mesh=mesh, stagger_learn=True,
                                  threshold=0.01)
        for i in range(n):
            reg.add_stream(f"s{i}")
        reg.finalize()
        with tempfile.NamedTemporaryFile("r", suffix=".jsonl") as f:
            stats = live_loop(_feed, reg, n_ticks=ticks, cadence_s=0.0,
                              alert_path=f.name, pipeline_depth=2,
                              dispatch_threads=2, micro_chunk=3,
                              chunk_stagger=True)
            # alert lines only: watchdog event lines (missed_tick) share
            # the file and carry a wall-clock elapsed_s, which differs
            # between any two runs and says nothing about the mesh
            lines = sorted(ln for ln in f.read().splitlines()
                           if not ln.startswith('{"event"'))
        assert stats["scored"] == n * ticks
        assert stats["alerts"] > 0, "emission comparison must be non-vacuous"
        final = [jax.device_get(g.state) for g in reg.groups]
        out[mode] = (lines, final)
    assert out["plain"][0] == out["mesh"][0]
    for s1, s2 in zip(out["plain"][1], out["mesh"][1]):
        for key in s1:
            np.testing.assert_array_equal(
                np.asarray(s1[key]), np.asarray(s2[key]), err_msg=key)
