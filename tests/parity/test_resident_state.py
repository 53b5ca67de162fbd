"""The state in the kernel's form between programs (ops/resident.py) against
the public [C, K, S, M] layout, which is the parity harness's and the
oracle's: every entry point of ops/step.py hands a tree back in the form it
arrived in, and the two forms step bit-equal — scores and every leaf — at
the four presets' row shapes (192 lanes, 192 at 32 columns, 384, 16,384:
the NAB preset's own rows over 128 columns, so the CPU backend steps them in
seconds), learning and inferring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.config import cluster_preset, node_preset, scaled_cluster_preset, scaled_nab_preset
from rtap_tpu.models.state import init_state
from rtap_tpu.ops.resident import host_resident
from rtap_tpu.ops.step import _enter_kernel, chunk_step, fused_step, group_step, replicate_state

G, TICKS = 2, 16

PRESETS = {
    "cluster": cluster_preset,
    "cluster32": lambda: scaled_cluster_preset(32),
    "node3": lambda: node_preset(3),
    "nab_rows": lambda: scaled_nab_preset(128),
}


@pytest.fixture(scope="module", params=list(PRESETS))
def case(request):
    """(cfg, public group state on the host after 8 learning ticks, feed)."""
    cfg = PRESETS[request.param]()
    rng = np.random.default_rng(44)
    values = (50 + 30 * np.sin(np.arange(8 + TICKS)[:, None, None] / 3.0)
              + rng.normal(0, 2, (8 + TICKS, G, cfg.n_fields))).astype(np.float32)
    ts = (1_700_000_000 + np.arange(8 + TICKS)[:, None]
          + np.zeros((1, G), np.int64)).astype(np.int32)
    state = replicate_state(init_state(cfg, 7), G)
    state, _ = chunk_step(jax.device_put(state), jnp.asarray(values[:8]),
                          jnp.asarray(ts[:8]), cfg, learn=True)
    return cfg, jax.device_get(state), values[8:], ts[8:]


def _as(tree: dict, cfg, resident: bool) -> dict:
    """A fresh device copy of the host tree `tree` in the asked-for form."""
    return jax.device_put(host_resident(tree, cfg.tm) if resident else tree)


def _public(tree: dict, cfg) -> dict:
    tree = jax.device_get(tree)
    return tm_tpu.public_form(tree, cfg.tm) if tm_tpu.kernel_resident(tree) else tree


def _run(program: str, state: dict, values, ts, cfg, learn: bool):
    """TICKS ticks through one entry point -> (state, scores [TICKS, G])."""
    if program in ("chunk_t1", "chunk_t8"):
        T = 1 if program == "chunk_t1" else 8
        out = []
        for i in range(0, TICKS, T):
            state, raw = chunk_step(state, jnp.asarray(values[i:i + T]),
                                    jnp.asarray(ts[i:i + T]), cfg, learn=learn)
            out.append(np.asarray(raw))
        return state, np.concatenate(out)
    out = []
    for i in range(TICKS):
        state, raw = group_step(state, jnp.asarray(values[i]), jnp.asarray(ts[i]),
                                cfg, learn=learn)
        out.append(np.asarray(raw))
    return state, np.stack(out)


@pytest.mark.parametrize("learn", [True, False], ids=["learning", "inferring"])
@pytest.mark.parametrize("program", ["chunk_t1", "chunk_t8", "group_step"])
def test_resident_input_steps_bit_equal_to_public_input(case, program, learn):
    """The same 16 ticks from the same state in both forms, and — across the
    three parametrised programs — T = 1 x 16 equal to T = 8 x 2 equal to
    `group_step` x 16: each is held to the public chunk of 8."""
    cfg, start, values, ts = case
    want_state, want_raw = _run("chunk_t8", _as(start, cfg, False), values, ts, cfg, learn)
    assert not tm_tpu.kernel_resident(want_state)  # public in, public out
    got_state, got_raw = _run(program, _as(start, cfg, True), values, ts, cfg, learn)
    assert tm_tpu.kernel_resident(got_state)       # resident in, resident out
    np.testing.assert_array_equal(got_raw, want_raw)
    want, got = _public(want_state, cfg), _public(got_state, cfg)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    if learn:  # the ticks really learned: the comparison is of moved state
        assert not np.array_equal(want["syn_perm"], start["syn_perm"])


@pytest.mark.parametrize("learn", [True, False], ids=["learning", "inferring"])
def test_single_stream_step_in_both_forms(case, learn):
    cfg, start, values, ts = case
    one = {k: v[0] for k, v in start.items()}
    states, raws = [], []
    for resident in (False, True):
        state, raw = _as(one, cfg, resident), []
        for i in range(TICKS):
            state, r = fused_step(state, jnp.asarray(values[i, 0]), jnp.int32(ts[i, 0]),
                                  cfg, learn)
            raw.append(float(r[0] if cfg.classifier.enabled else r))
        assert tm_tpu.kernel_resident(state) == resident
        states.append(_public(state, cfg))
        raws.append(raw)
    assert raws[0] == raws[1]
    for k in states[0]:
        np.testing.assert_array_equal(states[1][k], states[0][k], err_msg=k)


def test_round_trip_is_the_identity_on_every_leaf(case):
    """public -> resident -> public gives every leaf back, with and without
    a stream axis, on the host (views) and on the device."""
    cfg, start, _values, _ts = case
    wide = tm_tpu.wide_rows(cfg.tm)
    tm = cfg.tm
    K, S, M = tm.cells_per_column, tm.max_segments_per_cell, tm.max_synapses_per_segment
    for tree in (start, {k: v[0] for k, v in start.items()}):
        lead = np.shape(tree["prev_active"])[:-2]
        C = cfg.sp.columns
        for put in (lambda t: t, jax.device_put):
            turned = tm_tpu.resident_form(put(tree), tm)
            assert tm_tpu.kernel_resident(turned) and not tm_tpu.kernel_resident(tree)
            assert turned["presyn"].shape == (
                (*lead, C, M, K * S) if wide else (*lead, C, K * S * M))
            assert turned["seg_last"].shape == (*lead, C, K * S)
            # a program's boundary passes a resident tree untouched, both ways
            entered, leave = _enter_kernel(turned, cfg)
            assert entered is turned and leave(turned) is turned
            back = tm_tpu.public_form(turned, tm)
            assert set(back) == set(tree)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(back[k]), tree[k], err_msg=k)


def test_the_drivers_entry_traces_and_hands_back_the_public_shapes():
    """`__graft_entry__.py:entry()` is the one caller outside the tests that
    hands a WIDE public tree to a one-tick program (`fused_step` at the NAB
    width, one stream): the program converts it at its boundary and gives
    it back public, leaf for leaf — traced only, nothing runs."""
    import __graft_entry__ as graft

    fn, args = graft.entry()
    state = args[0]
    assert state["presyn"].ndim == 4 and not tm_tpu.kernel_resident(state)
    stepped, raw = jax.eval_shape(fn, *args)
    assert raw.shape == () and raw.dtype == jnp.float32
    assert stepped.keys() == state.keys()
    for k, v in state.items():
        assert (stepped[k].shape, stepped[k].dtype) == (v.shape, v.dtype), k
