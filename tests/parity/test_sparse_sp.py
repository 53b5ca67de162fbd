"""Sparse member-index SP pool parity (ISSUE 18): oracle vs device twins
over the gather-addressed layout, bit-exact across every permanence domain
(f32 / u16 / u8), through the vmapped group-chunk path, and on the edge
rows the layout introduces (all-empty and completely-full member tables).
Also pins the migration invariant: a dense pool re-laid by
models/migrate.sparsify_sp_state scores bit-identically to the dense
original forever (same synapses, same permanences, order-independent
integer overlap).

The per-slot SDR bit is computed with no gather (ISSUE 26): the helper is
held to the plain `sdr[members]` over every shape class it serves, and the
lowered sparse `sp_step` may hold no `gather` op.

Twin coverage: `sp_overlap` and `sp_compute` (oracle names) against
ops/sp_tpu.py's `sp_overlap` / `sp_step` — the same pairs the dense parity
file exercises, now on the sparse branch of each kernel.
"""

import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rtap_tpu.config import ModelConfig, RDSEConfig, SPConfig, cluster_preset, dense_cluster_preset
from rtap_tpu.models.migrate import sparse_pool_width, sparsify_config, sparsify_sp_state
from rtap_tpu.models.oracle.spatial_pooler import sp_compute, sp_overlap
from rtap_tpu.models.state import init_state, members_dtype
from rtap_tpu.ops.sp_tpu import _sdr_at_members, sp_step

SP_KEYS = ("perm", "boost", "overlap_duty", "active_duty", "sp_iter", "members")


def _sparse_cfg(perm_bits: int = 0, pool_members: int = 0) -> ModelConfig:
    return ModelConfig(
        rdse=RDSEConfig(size=64, active_bits=5, resolution=0.5),
        sp=SPConfig(columns=128, num_active_columns=8, potential_pct=0.5,
                    sparse_pool=True, pool_members=pool_members,
                    perm_bits=perm_bits),
    )


def _device_state(state):
    return {k: jnp.asarray(state[k]) for k in SP_KEYS}


def _sdr(rng, n_in, frac=0.05):
    sdr = np.zeros(n_in, bool)
    sdr[rng.choice(n_in, size=max(1, int(frac * n_in)), replace=False)] = True
    return sdr


def _run_parity(cfg: ModelConfig, n_steps: int, learn: bool, host=None):
    rng = np.random.default_rng(7)
    host = init_state(cfg, seed=3) if host is None else host
    dev = _device_state(copy.deepcopy(host))
    for step in range(n_steps):
        sdr = _sdr(rng, cfg.input_size)
        host_active = sp_compute(host, sdr, cfg.sp, learn=learn)
        dev, dev_active = sp_step(dev, jnp.asarray(sdr), cfg.sp, learn=learn)
        np.testing.assert_array_equal(
            host_active, np.asarray(dev_active), err_msg=f"step {step}")
        np.testing.assert_array_equal(
            host["perm"], np.asarray(dev["perm"]), err_msg=f"step {step}")
        np.testing.assert_array_equal(host["overlap_duty"], np.asarray(dev["overlap_duty"]))
        np.testing.assert_array_equal(host["active_duty"], np.asarray(dev["active_duty"]))
    assert int(host["sp_iter"]) == int(dev["sp_iter"]) == (n_steps if learn else 0)
    return host


@pytest.mark.parametrize("perm_bits", [0, 16, 8])
@pytest.mark.parametrize("learn", [True, False])
def test_sparse_sp_parity_all_domains(perm_bits, learn):
    """Gather-addressed overlap + learning bit-exact oracle-vs-device in
    every permanence domain (f32 arithmetic and int32 quanta arithmetic)."""
    _run_parity(_sparse_cfg(perm_bits), n_steps=100, learn=learn)


def test_sparse_sp_parity_cluster_preset():
    """The shipping geometry itself (C=256, P=64, u16)."""
    cfg = cluster_preset()
    assert cfg.sp.sparse_pool and cfg.sp_members == 64
    _run_parity(cfg, n_steps=40, learn=True)


@pytest.mark.parametrize("perm_bits", [0, 16])
def test_sparse_vmapped_chunk_parity(perm_bits):
    """The group path: sp_step vmapped over a stacked [G, ...] state (how
    the fused chunk kernel consumes the pool) matches G independent oracle
    streams bit-for-bit."""
    cfg = _sparse_cfg(perm_bits)
    G, n_steps = 4, 30
    hosts = [init_state(cfg, seed=10 + g) for g in range(G)]
    dev = {k: jnp.stack([jnp.asarray(h[k]) for h in hosts]) for k in SP_KEYS}
    step = jax.vmap(lambda st, sdr: sp_step(st, sdr, cfg.sp, learn=True))
    rng = np.random.default_rng(12)
    for t in range(n_steps):
        sdrs = np.stack([_sdr(rng, cfg.input_size) for _ in range(G)])
        host_active = np.stack(
            [sp_compute(hosts[g], sdrs[g], cfg.sp, learn=True) for g in range(G)])
        dev, dev_active = step(dev, jnp.asarray(sdrs))
        np.testing.assert_array_equal(host_active, np.asarray(dev_active), err_msg=f"t {t}")
    for g in range(G):
        np.testing.assert_array_equal(hosts[g]["perm"], np.asarray(dev["perm"][g]))
        np.testing.assert_array_equal(hosts[g]["members"], np.asarray(dev["members"][g]))


def test_empty_and_full_pool_edge_rows():
    """Padding semantics: an all-empty member row (every slot -1, the
    migration pad extreme) contributes overlap 0 and its permanences stay
    exactly 0 through learning and the weak-column bump on BOTH backends;
    a completely full row behaves like a dense column of the same members."""
    cfg = _sparse_cfg(perm_bits=16)
    host = init_state(cfg, seed=3)
    P = cfg.sp_members
    host["members"][0, :] = np.int16(-1)   # empty pool row
    host["perm"][0, :] = 0
    host["members"][1, :] = np.arange(P, dtype=members_dtype(cfg))  # full row
    dev = _device_state(copy.deepcopy(host))
    rng = np.random.default_rng(5)
    for t in range(60):
        sdr = _sdr(rng, cfg.input_size, frac=0.2)
        ho = sp_overlap(host, sdr, cfg.sp)
        assert ho[0] == 0, "empty pool row must never overlap"
        host_active = sp_compute(host, sdr, cfg.sp, learn=True)
        dev, dev_active = sp_step(dev, jnp.asarray(sdr), cfg.sp, learn=True)
        np.testing.assert_array_equal(host_active, np.asarray(dev_active), err_msg=f"t {t}")
        assert not host["perm"][0].any(), "empty slots must stay at permanence 0"
    np.testing.assert_array_equal(host["perm"], np.asarray(dev["perm"]))
    np.testing.assert_array_equal(host["members"], np.asarray(dev["members"]))


@pytest.mark.parametrize("perm_bits", [0, 16, 8])
def test_migrated_pool_scores_match_dense(perm_bits):
    """models/migrate.py invariant: the re-laid pool is the SAME pool —
    overlap, winners, and learned permanences track the dense original
    bit-for-bit through learning (the committed-checkpoint restore in
    tests/unit/test_checkpoint.py pins the end-to-end version)."""
    base = dense_cluster_preset(perm_bits=perm_bits)
    cfg = dataclasses.replace(
        base, sp=dataclasses.replace(base.sp, columns=128))
    dense = init_state(cfg, seed=5)
    P = sparse_pool_width(dense["potential"])
    scfg = sparsify_config(cfg, P)
    sparse = sparsify_sp_state({k: np.copy(v) for k, v in dense.items()}, P)
    rng = np.random.default_rng(11)
    for t in range(50):
        sdr = _sdr(rng, cfg.input_size, frac=0.08)
        np.testing.assert_array_equal(
            sp_overlap(dense, sdr, cfg.sp), sp_overlap(sparse, sdr, scfg.sp),
            err_msg=f"t {t}")
        a_d = sp_compute(dense, sdr, cfg.sp, learn=True)
        a_s = sp_compute(sparse, sdr, scfg.sp, learn=True)
        np.testing.assert_array_equal(a_d, a_s, err_msg=f"t {t}")
    # learned permanences agree slot-for-slot on the member table
    order = np.argsort(~dense["potential"], axis=-1, kind="stable")[:, :P]
    valid = np.take_along_axis(dense["potential"], order, axis=-1)
    np.testing.assert_array_equal(
        np.where(valid, np.take_along_axis(dense["perm"], order, axis=-1), 0),
        sparse["perm"])


def _members(rng, rows: str, C: int, P: int, n_in: int, dtype) -> np.ndarray:
    pool = rng.integers(0, n_in, size=(C, P))
    pool[:, -1] = n_in - 1  # the last input, in the padded tail of the last word
    if rows == "empty":
        pool[:] = -1
    elif rows == "mixed":
        pool[rng.random((C, P)) < 0.4] = -1
        pool[0, :] = -1
    return pool.astype(dtype)


@pytest.mark.parametrize("vmapped", [False, True], ids=["single", "vmapped"])
@pytest.mark.parametrize("sdr_kind", ["zeros", "ones", "random"])
@pytest.mark.parametrize("rows", ["empty", "full", "mixed"])
@pytest.mark.parametrize("dtype", [np.int16, np.int32], ids=["i16", "i32"])
@pytest.mark.parametrize("n_in", [33, 100, 128, 400])
def test_sdr_at_members_equals_gather(n_in, dtype, rows, sdr_kind, vmapped):
    """The packed-word bit test is the gather `sdr[max(members, 0)]`, bit
    for bit: n_in below, at and across word boundaries, both member dtypes,
    empty / full / mixed rows, and the two constant SDRs."""
    rng = np.random.default_rng(n_in)
    G, C, P = (3 if vmapped else 1), 6, 9
    pool = np.stack([_members(rng, rows, C, P, n_in, dtype) for _ in range(G)])
    sdr = {"zeros": np.zeros((G, n_in), bool), "ones": np.ones((G, n_in), bool),
           "random": rng.random((G, n_in)) < 0.3}[sdr_kind]
    want = np.stack([sdr[g][np.maximum(pool[g], 0)] for g in range(G)])
    if vmapped:
        got = jax.vmap(_sdr_at_members)(jnp.asarray(pool), jnp.asarray(sdr))
    else:
        got = _sdr_at_members(jnp.asarray(pool[0]), jnp.asarray(sdr[0]))[None]
    assert got.dtype == jnp.bool_
    np.testing.assert_array_equal(want, np.asarray(got))


def test_sparse_sp_step_lowers_without_gather():
    """The element-wise gather ran at 0.05 % of its bytes' cost on a v5e
    (PERF.md §6, PR 26): the lowered sparse step, learning on, vmapped as
    the chunk kernel runs it, holds no gather op at all."""
    cfg = cluster_preset()
    host = init_state(cfg, seed=0)
    dev = {k: jnp.stack([jnp.asarray(host[k])] * 2) for k in SP_KEYS}
    sdrs = jnp.zeros((2, cfg.input_size), bool)
    step = jax.jit(jax.vmap(lambda st, sdr: sp_step(st, sdr, cfg.sp, learn=True)))
    assert "gather" not in step.lower(dev, sdrs).as_text()
