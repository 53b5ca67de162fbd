"""Synapse growth places each chosen winner by matching ranks (ISSUE 28):
`ops/tm_tpu.py:_grow_compact` against the gather form it replaced — kept
here as the reference, as tests/parity/test_sparse_sp.py keeps the SP's —
and against the oracle's `_grow_synapses`, element for element."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.config import TMConfig, cluster_preset, scaled_cluster_preset
from rtap_tpu.models.oracle.temporal_memory import _grow_synapses
from rtap_tpu.models.perm import tm_domain
from rtap_tpu.models.state import init_state

from tests.parity.test_tpu_paths import force_tpu_paths  # noqa: F401 — fixture

# (L, M, W, new_synapse_count, n_cells): learn_cap, synapse slots a segment,
# winner list length (col_cap x cells a column), growth a step, cells
SHAPES = {
    "cluster256": (64, 12, 80, 10, 2048),
    "cluster32": (64, 12, 24, 3, 256),
    "nab_small": (40, 32, 48, 20, 2048),  # M = 32 > new_synapse_count, as at the NAB width
    "grow_over_w": (8, 12, 4, 10, 64),  # new_synapse_count > W
}
CFG = TMConfig(perm_bits=16)  # integer quanta: eviction meets ties
P_INIT = np.float32(tm_domain(CFG).rate(CFG.initial_permanence))


def _grow_compact_gather(presyn_l, perm_l, n_grow, winner_ids, n_cells,
                         initial_perm, G):
    """`_grow_compact` as it stood before ISSUE 28: the chosen winners'
    positions sorted to the front, then two element-wise gathers."""
    L, M = presyn_l.shape
    W = winner_ids.shape[0]
    valid_w = winner_ids < n_cells
    already = (presyn_l[:, None, :] == winner_ids[None, :, None]).any(-1)
    eligible = valid_w[None, :] & ~already
    rank = jnp.cumsum(eligible, axis=1)
    chosen = eligible & (rank <= n_grow[:, None])
    n_new = chosen.sum(-1).astype(jnp.int32)

    wpos = jnp.where(chosen, jnp.arange(W, dtype=jnp.int32), W)
    wpos = jax.lax.sort(wpos, dimension=1)[:, :G]
    if G > W:
        wpos = jnp.concatenate([wpos, jnp.full((L, G - W), W, jnp.int32)], axis=1)
    new_ids = jnp.where(wpos < W, winner_ids[jnp.clip(wpos, 0, W - 1)], n_cells)

    occupied = presyn_l >= 0
    short = n_new - (M - occupied.sum(-1))
    key = jnp.where(occupied, perm_l, np.float32(np.inf))
    ranks = jnp.argsort(jnp.argsort(key, axis=-1, stable=True), axis=-1, stable=True)
    evict = occupied & (ranks < short[:, None])
    presyn_l = jnp.where(evict, -1, presyn_l)
    perm_l = jnp.where(evict, 0.0, perm_l)

    free = presyn_l < 0
    frank = jnp.cumsum(free, axis=-1) - 1
    assign = free & (frank < n_new[:, None])
    fill = new_ids[jnp.arange(L)[:, None], jnp.clip(frank, 0, G - 1)]
    return jnp.where(assign, fill, presyn_l), jnp.where(assign, initial_perm, perm_l)


def _case(rng, shape: str, grow: str, free: str, winners: str):
    """One call's operands: (presyn_l, perm_l, n_grow, winner_ids)."""
    L, M, W, G, N = SHAPES[shape]
    if winners == "empty":
        n_valid = 0
    elif grow == "above":
        n_valid = max(1, min(W, G) - 1)  # fewer eligible than asked for
    else:
        n_valid = int(rng.integers(1, W + 1))
    ids = np.sort(rng.choice(N, size=n_valid, replace=False)).astype(np.int32)
    winner_ids = np.concatenate([ids, np.full(W - n_valid, N, np.int32)])

    presyn = np.full((L, M), -1, np.int32)
    perm = np.zeros((L, M), np.float32)
    pool = np.setdiff1d(np.arange(N, dtype=np.int32), ids)  # cells that are no winner
    for row in range(L):
        if free == "all":
            continue
        n_occ = M if free == "none" else int(rng.integers(0, M))
        if winners == "already" and n_valid:
            # half the occupied slots (at most) hold winners already
            take = rng.choice(ids, size=min(n_valid, (n_occ + 1) // 2), replace=False)
            rest = rng.choice(pool, size=n_occ - len(take), replace=False)
            cells = rng.permutation(np.concatenate([take, rest]))
        else:
            cells = rng.choice(pool, size=n_occ, replace=False)
        slots = rng.choice(M, size=n_occ, replace=False)
        presyn[row, slots] = cells
        # few distinct quanta: the weakest is a tie, broken by slot
        perm[row, slots] = rng.integers(1, 4, size=n_occ) * 1000

    eligible = (~np.isin(winner_ids[None, :], [N])
                & ~(presyn[:, None, :] == winner_ids[None, :, None]).any(-1)).sum(-1)
    if grow == "nonpos":
        n_grow = rng.integers(-3, 1, size=L)
    elif grow == "below":
        n_grow = np.clip(eligible // 2, 1, G)
    else:
        n_grow = np.full(L, G)
    return presyn, perm, n_grow.astype(np.int32), winner_ids


def _oracle(presyn, perm, n_grow, winner_ids, N):
    out_presyn, out_perm = presyn.copy(), perm.copy()
    candidates = winner_ids[winner_ids < N]
    for row in range(presyn.shape[0]):
        state = {"presyn": presyn[row].reshape(1, 1, 1, -1).copy(),
                 "syn_perm": perm[row].astype(np.uint16).reshape(1, 1, 1, -1)}
        _grow_synapses(state, 0, 0, 0, candidates, int(n_grow[row]), CFG)
        out_presyn[row] = state["presyn"][0, 0, 0]
        out_perm[row] = state["syn_perm"][0, 0, 0]
    return out_presyn, out_perm


@functools.lru_cache(maxsize=None)
def _compiled(which: str, shape: str, vmapped: bool, force: bool):
    """One trace per (form, shape, batching, strategy). `_grow_compact` has
    no per-backend branch left (ISSUE 28); FORCE_TPU_PATHS is still set both
    ways before the first call, so one that comes back is held to both."""
    N, G = SHAPES[shape][4], SHAPES[shape][3]
    if which == "new":
        cfg = dataclasses.replace(CFG, new_synapse_count=G)
        fn = lambda a, b, c, d: tm_tpu._grow_compact(cfg, a, b, c, d, N, P_INIT)  # noqa: E731
    else:
        fn = lambda a, b, c, d: _grow_compact_gather(a, b, c, d, N, P_INIT, G)  # noqa: E731
    return jax.jit(jax.vmap(fn) if vmapped else fn)


@pytest.fixture(params=[False, True], ids=["cpu_paths", "tpu_paths"])
def force(request):
    old = tm_tpu.FORCE_TPU_PATHS
    tm_tpu.FORCE_TPU_PATHS = request.param
    yield request.param
    tm_tpu.FORCE_TPU_PATHS = old


@pytest.mark.parametrize("vmapped", [False, True], ids=["single", "vmapped"])
@pytest.mark.parametrize("winners", ["fresh", "already", "empty"])
@pytest.mark.parametrize("free", ["none", "some", "all"])
@pytest.mark.parametrize("grow", ["nonpos", "below", "above"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_grow_compact_equals_gather_form_and_oracle(shape, grow, free, winners,
                                                    vmapped, force):
    """Same presyn ids and permanences as the gather form and the oracle:
    eviction of the weakest (ties by slot), ascending fill, n_new."""
    rng = np.random.default_rng(
        [list(SHAPES).index(shape), len(grow), len(free), len(winners), vmapped])
    cases = [_case(rng, shape, grow, free, winners) for _ in range(3 if vmapped else 1)]
    ops = [np.stack(x) for x in zip(*cases)] if vmapped else list(cases[0])
    dev = [jnp.asarray(x) for x in ops]
    got = [np.asarray(x) for x in _compiled("new", shape, vmapped, force)(*dev)]
    ref = [np.asarray(x) for x in _compiled("gather", shape, vmapped, force)(*dev)]
    N = SHAPES[shape][4]
    want = [np.stack(x) for x in zip(*(_oracle(*c, N) for c in cases))]
    if not vmapped:
        want = [w[0] for w in want]
    for name, g, r, w in zip(("presyn", "perm"), got, ref, want):
        np.testing.assert_array_equal(g, r, err_msg=f"{name} vs the gather form")
        np.testing.assert_array_equal(g, w, err_msg=f"{name} vs the oracle")
    if winners == "empty" or grow == "nonpos":
        np.testing.assert_array_equal(got[0], ops[0])  # nothing grows


@pytest.mark.parametrize("preset", ["cluster", "scaled32"])
def test_tm_step_lowers_without_gather(force_tpu_paths, preset):
    """The two element-wise gathers of `_grow_compact` were 54-82 % of a
    cluster tick on a v5e (PERF.md §6, PR 28): the lowered `group_step`,
    learning on, in the forms the chip runs, holds no gather op at all."""
    from rtap_tpu.ops.step import group_step

    cfg = cluster_preset() if preset == "cluster" else scaled_cluster_preset(32)
    host = init_state(cfg, seed=0)
    state = {k: jnp.stack([jnp.asarray(v)] * 2) for k, v in host.items()}
    text = group_step.lower(state, jnp.zeros((2, cfg.n_fields), jnp.float32),
                            jnp.zeros((2,), jnp.int32), cfg, learn=True).as_text()
    assert "gather" not in text
