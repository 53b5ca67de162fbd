"""A `StreamGroup` holds its state on the device in the kernel's form
(`resident`, ops/resident.py) and reads it out as the public tree (`state`):
readers written against [G, C, K, S, M] keep their meaning, the checkpoint on
disk keeps the public layout, and the layouts are converted at the edges only
— set-up, a slot claimed, a checkpoint, a row read — each under the span
`rtap.state.relayout`, counted in `relayouts`; never in a dispatched chunk or
a live tick. The twin every case is held to is the public tree stepped by
`chunk_step` directly, as a group of the parent commit held it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rtap_tpu.ops.resident as resident
import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.config import cluster_preset, node_preset, scaled_cluster_preset, scaled_nab_preset
from rtap_tpu.models.state import init_state
from rtap_tpu.ops.step import chunk_step, replicate_state
from rtap_tpu.service.checkpoint import load_group, save_group
from rtap_tpu.service.registry import StreamGroup, segment_capacity

G, T, SEED = 3, 8, 5

PRESETS = {
    "cluster": cluster_preset,
    "cluster32": lambda: scaled_cluster_preset(32),
    "node3": lambda: node_preset(3),
    "nab_rows": lambda: scaled_nab_preset(128),  # the NAB preset's 16,384-lane rows
}


def _feed(cfg, chunks: int):
    rng = np.random.default_rng(17)
    n = chunks * T
    values = (50 + 30 * np.sin(np.arange(n)[:, None, None] / 3.0)
              + rng.normal(0, 2, (n, G, cfg.n_fields))).astype(np.float32)
    ts = (1_700_000_000 + np.arange(n)[:, None] + np.zeros((1, G), np.int64))
    return values, ts


@pytest.fixture(params=list(PRESETS))
def stepped(request):
    """(cfg, a group after two chunks, its public twin's state on the host)."""
    cfg = PRESETS[request.param]()
    values, ts = _feed(cfg, 2)
    group = StreamGroup(cfg, [f"s{i}" for i in range(G - 1)] + ["__pad0"],
                        seed=SEED, backend="tpu")
    twin = jax.device_put(replicate_state(init_state(cfg, SEED), G))
    for c in range(2):
        sl = slice(c * T, (c + 1) * T)
        group.run_chunk(values[sl], ts[sl])
        twin, _ = chunk_step(twin, jnp.asarray(values[sl]),
                             jnp.asarray(ts[sl].astype(np.int32)), cfg)
    return cfg, group, jax.device_get(twin)


def test_state_reads_as_the_public_tree(stepped):
    cfg, group, twin = stepped
    assert tm_tpu.kernel_resident(group.resident)
    assert set(group.state) == set(twin) and len(group.state) == len(twin)
    for k, want in twin.items():
        leaf = group.state[k]
        assert leaf.shape == want.shape and leaf.dtype == want.dtype, k
        np.testing.assert_array_equal(np.asarray(leaf), want, err_msg=k)
        for slot in (0, G - 1):
            np.testing.assert_array_equal(np.asarray(group.state[k][slot]),
                                          want[slot], err_msg=k)
    np.testing.assert_array_equal(group.state["syn_perm"][1:, 3], twin["syn_perm"][1:, 3])
    np.testing.assert_array_equal(group.state["seg_last"][0, 2, 1], twin["seg_last"][0, 2, 1])
    # fetched whole, as a checkpoint fetches it
    host = jax.device_get(group.state)
    for k, want in twin.items():
        np.testing.assert_array_equal(np.asarray(host[k]), want, err_msg=k)
    assert group.capacity_stats() == segment_capacity(twin["seg_last"] >= 0)
    assert int(np.asarray(group.state["tm_overflow"]).sum()) == int(twin["tm_overflow"].sum())


def test_a_claimed_slot_holds_a_fresh_stream_in_its_row(stepped):
    cfg, group, twin = stepped
    before = group.relayouts
    slot = group.claim_slot("late")
    assert slot == G - 1 and group.relayouts == before + 1
    fresh = init_state(cfg, SEED)
    for k, want in twin.items():
        got = np.asarray(group.state[k])
        np.testing.assert_array_equal(got[slot], fresh[k], err_msg=k)
        np.testing.assert_array_equal(got[:slot], want[:slot], err_msg=k)


def test_assigning_public_leaves_converts_them_once_there(stepped):
    cfg, group, twin = stepped
    shapes = {k: v.shape for k, v in group.resident.items()}
    n0 = group.relayouts
    group.state = {**group.state, "enc_resolution": group.state["enc_resolution"]}
    assert group.relayouts == n0  # its own leaves come back as they are
    group.state["perm"] = group.state["perm"]
    assert group.relayouts == n0  # nor is a leaf outside the kernel's six
    group.state["syn_perm"] = jnp.asarray(twin["syn_perm"])
    assert group.relayouts == n0 + 1
    group.state = {k: jnp.asarray(v) for k, v in twin.items()}
    assert group.relayouts == n0 + 2
    assert {k: v.shape for k, v in group.resident.items()} == shapes
    for k, want in twin.items():
        np.testing.assert_array_equal(np.asarray(group.state[k]), want, err_msg=k)
    with pytest.raises(TypeError):
        del group.state["perm"]


def test_checkpoint_keeps_the_public_layout_and_resumes_bit_equal(stepped, tmp_path):
    import orbax.checkpoint as ocp

    cfg, group, twin = stepped
    save_group(group, tmp_path / "grp")
    with ocp.PyTreeCheckpointer() as ckptr:
        stored = ckptr.restore(tmp_path / "grp" / "state")["model"]
    assert set(stored) == set(twin)
    for k, want in twin.items():  # the file a group of the parent commit wrote
        assert np.asarray(stored[k]).dtype == want.dtype, k
        np.testing.assert_array_equal(np.asarray(stored[k]), want, err_msg=k)
    resumed = load_group(tmp_path / "grp")
    assert tm_tpu.kernel_resident(resumed.resident)
    values, ts = _feed(cfg, 3)
    a = group.run_chunk(values[2 * T:], ts[2 * T:])
    b = resumed.run_chunk(values[2 * T:], ts[2 * T:])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for k in twin:
        np.testing.assert_array_equal(np.asarray(resumed.state[k]),
                                      np.asarray(group.state[k]), err_msg=k)


def test_the_layouts_meet_at_the_edges_only(monkeypatch, tmp_path):
    """`rtap.state.relayout` fires — with its leaves and bytes — where a
    state is made, a slot claimed, a checkpoint written and read, a row
    read; dispatched chunks, ticks and the live loop count none."""
    from rtap_tpu.service.loop import live_loop
    from rtap_tpu.service.registry import StreamGroupRegistry

    seen = []
    inner = resident.span

    def spy(name, *args, **counts):
        seen.append((name, counts))
        return inner(name, *args, **counts)

    monkeypatch.setattr(resident, "span", spy)
    cfg = scaled_cluster_preset(32)
    values, ts = _feed(cfg, 2)
    group = StreamGroup(cfg, ["a", "b", "__pad0"], seed=SEED, backend="tpu")
    per_stream = sum(np.asarray(v).nbytes for k, v in init_state(cfg, SEED).items()
                     if k in tm_tpu._KERNEL_KEYS)
    assert seen == [("rtap.state.relayout", {"leaves": 6, "bytes": per_stream})]
    assert group.relayouts == 1

    def edge(act, count=1):
        """`act()` converts `count` times, each under the span."""
        n_seen, n = len(seen), group.relayouts
        out = act()
        assert len(seen) - n_seen == count and group.relayouts - n == count
        assert {name for name, _ in seen} == {"rtap.state.relayout"}
        return out

    edge(lambda: group.run_chunk(values[:T], ts[:T]), 0)
    h = edge(lambda: group.dispatch_chunk(values[T:], ts[T:]), 0)
    edge(lambda: group.collect_chunk(h), 0)
    edge(lambda: group.tick(values[0], ts[0]), 0)
    edge(lambda: group.claim_slot("c"))
    edge(lambda: np.asarray(group.state["presyn"][1]))
    assert seen[-1][1] == {"leaves": 1, "bytes": group.resident["presyn"][1].nbytes}
    edge(lambda: np.asarray(group.state["tm_overflow"]), 0)
    edge(group.capacity_stats)
    edge(lambda: save_group(group, tmp_path / "grp"))
    n_seen = len(seen)
    back = load_group(tmp_path / "grp")
    assert len(seen) == n_seen + 1 and back.relayouts == 1  # loaded, none made first

    reg = StreamGroupRegistry(cfg, group_size=2, backend="tpu")
    for i in range(4):
        reg.add_stream(f"l{i}")
    reg.finalize()
    made = [g.relayouts for g in reg.groups]
    rng = np.random.default_rng(3)
    stats = live_loop(
        lambda k: ((30 + 5 * rng.random(4)).astype(np.float32), 1_700_000_000 + k),
        reg, n_ticks=5, cadence_s=0.0, aot_warmup=True)
    assert stats["ticks"] == 5
    assert [g.relayouts for g in reg.groups] == made
