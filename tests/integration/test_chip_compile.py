"""Described-topology compiles: the main path's programs, compiled by the
TPU's own compiler for a v5e that is described and not attached
(`on-chip-measurement` guide §2.3; ISSUE 21).

Interpret mode and the CPU lowering cannot show what these do: a kernel that
had passed every interpreter parity test was refused here for more VMEM than
a kernel may use. Nothing runs — a compile that passes is not a chip run —
but what the chip's compiler refuses costs no chip time to find. Model
widths are the real ones (cluster_preset: 256 columns x 8 cells;
scaled_cluster_preset(32)); the stream batch is 128, which keeps each
compile to seconds (G=1024 compiles were rehearsed for the PR, CHANGES.md).

`jax.default_backend()` is still the CPU here, so the test — not a program
option — steers the kernels onto their TPU branches (tm_tpu.FORCE_TPU_PATHS).
The persistent compilation cache is off around these compiles: an entry
written for a described device cannot be read back without a chip.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rtap_tpu.ops.tm_tpu as tm_tpu
from rtap_tpu.config import cluster_preset, nab_preset, node_preset, scaled_cluster_preset
from rtap_tpu.models.state import init_state

G = 128


@pytest.fixture(scope="module")
def v5e():
    """SingleDeviceSharding on one described v5e chip, TPU branches on."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    paths_was = tm_tpu.FORCE_TPU_PATHS
    tm_tpu.FORCE_TPU_PATHS = True
    # the formulation is baked into traced programs: a `tm_step` that an
    # earlier test file of this worker traced in the backend's forms (the
    # CPU's `nonzero`, a scatter) must not serve these compiles
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    tm_tpu.FORCE_TPU_PATHS = paths_was
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _shapes(tree: dict, sharding, g: int = G) -> dict:
    return {k: jax.ShapeDtypeStruct((g, *np.shape(v)), np.asarray(v).dtype,
                                    sharding=sharding)
            for k, v in tree.items()}


def _step_args(cfg, sharding, T=None, predict=0, g: int = G):
    state = _shapes(init_state(cfg, 0, predict_horizon=predict), sharding, g)
    lead = () if T is None else (T,)
    vals = jax.ShapeDtypeStruct((*lead, g, cfg.n_fields), jnp.float32,
                                sharding=sharding)
    ts = jax.ShapeDtypeStruct((*lead, g), jnp.int32, sharding=sharding)
    return state, vals, ts


@pytest.mark.parametrize("preset", ["cluster", "scaled32"])
def test_default_chunk_step_compiles_for_v5e(v5e, preset):
    """The default program (replay/bench/chip_smoke score path), learning
    on, at both supported presets: pure XLA, no custom call."""
    from rtap_tpu.ops.step import chunk_step

    cfg = cluster_preset() if preset == "cluster" else scaled_cluster_preset(32)
    compiled = chunk_step.lower(*_step_args(cfg, v5e, T=2), cfg,
                                learn=True).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    # the state is an argument the program really holds on the device
    per_stream = sum(np.asarray(v).nbytes for v in init_state(cfg, 0).values())
    assert compiled.memory_analysis().argument_size_in_bytes >= G * per_stream


@pytest.mark.parametrize("preset", ["cluster", "scaled32"])
def test_cluster_chunk_step_holds_no_gather_at_the_cells_batch(v5e, preset):
    """At the benchmark cells' batch of 1,024 streams the optimised program
    holds no gather: the two in `_grow_compact` were 54-82 % of a tick on the
    chip at ~10 ns an element (ISSUE 28; PERF.md §6)."""
    from rtap_tpu.ops.step import chunk_step

    cfg = cluster_preset() if preset == "cluster" else scaled_cluster_preset(32)
    compiled = chunk_step.lower(*_step_args(cfg, v5e, T=2, g=1024), cfg,
                                learn=True).compile()
    assert not re.findall(r"= \S+ gather\(", compiled.as_text())


@pytest.mark.parametrize("program", ["chunk_step", "group_step"])
@pytest.mark.parametrize("preset", ["cluster", "scaled32"])
def test_cluster_step_holds_no_scatter_at_the_cells_batch(v5e, preset, program):
    """Nor a scatter, in the replay cells' program or the live cell's: the
    three index-list -> mask writes (TM best matching segment, SP winners,
    encoder bits) were the largest single op of both cluster ticks, unscoped,
    at 5.4-6.8 ns an update (ISSUE 31; PERF.md §6). Masks are compares."""
    import rtap_tpu.ops.step as step

    cfg = cluster_preset() if preset == "cluster" else scaled_cluster_preset(32)
    T = 2 if program == "chunk_step" else None
    compiled = getattr(step, program).lower(
        *_step_args(cfg, v5e, T=T, g=1024), cfg, learn=True).compile()
    assert " scatter(" not in compiled.as_text()


@pytest.mark.parametrize("preset", ["cluster", "scaled32"])
def test_cluster_chunk_step_sweeps_the_pool_in_one_fusion(v5e, preset):
    """What the chip's compiler makes of the element-wise membership test
    (ISSUE 36): the punish sweep, synapse death and the dendrite sweep are
    ONE fusion that takes the pools and gives them back beside the two
    count operands — no op of the program has a 32-bit pool-shaped result
    (the parent wrote the decoded ids and each sweep's mask grid to HBM as
    `s32[G,C,192]`, four fusions a tick), and exactly one fusion's result
    holds both pools."""
    from rtap_tpu.ops.step import chunk_step

    cfg = cluster_preset() if preset == "cluster" else scaled_cluster_preset(32)
    text = chunk_step.lower(*_step_args(cfg, v5e, T=2), cfg,
                            learn=True).compile().as_text()
    pool = rf"\[{G},{cfg.sp.columns},192\]"
    results = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) fusion\(", text, re.M)
    assert len(results) > 50  # a whole step's fusions were read
    assert not [r for r in results if re.search("s32" + pool, r)]
    both = [r for r in results
            if re.search("s16" + pool, r) and re.search("u16" + pool, r)]
    assert len(both) == 1 and both[0].count("pred[") == 2, both


def _scan_body(text: str) -> str:
    """The computation the program's `while` (the scan over the chunk's
    ticks) names as its body — the entry computation's own loop, not the
    small ones inside sorts and cumulative sums."""
    entry = text[text.index("\nENTRY "):]
    bodies = re.findall(r" while\(.*?body=%?([\w.\-]+)", entry)
    assert bodies, "the compiled chunk program holds no while loop"
    found = []
    for name in bodies:
        start = re.search(rf"^%?{re.escape(name)} \(", text, re.M).start()
        found.append(text[start:text.index("\n}\n", start)])
    return max(found, key=len)


@pytest.mark.parametrize("preset", ["cluster", "scaled32", "node3"])
def test_chunk_step_relays_no_pool_inside_the_scan(v5e, preset):
    """The scan's carry and its loop body agree on the layout of every
    `[G, C, K*S*M]` leaf: the `while` body holds no `copy` with a 16-bit
    pool-shaped result. Where a pool row fills whole 128-lane tiles
    (`node_preset(3)`: 384 lanes) the workspace gather's one-hot matmul was
    the one consumer that wanted the columns minor, and held the carry of
    all four such leaves — the dense SP's `perm` among them — in a layout
    the rest of the body re-laid every tick: eight pool-shaped copies, six
    of them 16-bit, 5.3 ms of a 21.6 ms group-tick (ISSUE 38; PERF.md §6).
    There the gather is a compare-select reduce in the pools' own types
    (`tm_tpu.gather_by_select`), so no f32 pool exists in the body either;
    at 192 lanes the matmul stays and the body shares its layout."""
    from rtap_tpu.ops.step import chunk_step

    cfg = {"cluster": cluster_preset, "scaled32": lambda: scaled_cluster_preset(32),
           "node3": lambda: node_preset(3)}[preset]()
    tm = cfg.tm
    lanes = tm.cells_per_column * tm.max_segments_per_cell * tm.max_synapses_per_segment
    assert tm_tpu.gather_by_select(tm) == (preset == "node3")
    body = _scan_body(chunk_step.lower(*_step_args(cfg, v5e, T=2), cfg,
                                       learn=True).compile().as_text())
    results = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(", body, re.M)
    assert len(results) > 200  # a whole tick's instructions were read
    pool = rf"\[{G},{cfg.sp.columns},{lanes}\]"
    assert any(re.match("[su]16" + pool, ty) for ty, _ in results)  # the pattern bites
    assert not [ty for ty, op in results
                if op == "copy" and re.match("[su]16" + pool, ty)]
    if preset == "node3":
        assert not [ty for ty, _ in results if re.search("f32" + pool, ty)]


@pytest.fixture(scope="module")
def nab_chunk(v5e):
    """`chunk_step` at the published NAB width and the benchmark cell's batch
    (17 streams), T = 2, learning on, as the chip's compiler makes it — one
    compile (~20 s) for the tests that read it."""
    from rtap_tpu.ops.step import chunk_step

    cfg = nab_preset(0.0, 100.0)
    assert tm_tpu.wide_rows(cfg.tm) and not tm_tpu.wide_rows(cluster_preset().tm)
    return chunk_step.lower(*_step_args(cfg, v5e, T=2, g=17), cfg,
                            learn=True).compile()


def test_nab_chunk_step_relays_no_pool_inside_the_scan(nab_chunk):
    """At wide rows the kernel holds the pools `[C, M, K*S]`, so a column's
    row — what the learning workspace's indexed moves take and put back — is
    one contiguous 64 KiB block, and the fused sweep runs in the same layout
    (K*S = 512 on the lanes, M = 32 on the sublanes: whole tiles both): the
    `while` body holds no `copy` and no `transpose` with a pool-sized result.
    With the public `[C, K, S, M]` layout in the kernel it held four a tick
    (`{s32,f32}[17,2048,16384]`, rows contiguous for the moves and back to
    columns-minor for the sweep: 27 of a 76 ms group-tick, ISSUE 40); they
    stand in ENTRY now, once a program (`rtap.layout`)."""
    text = nab_chunk.as_text()
    cfg = nab_preset(0.0, 100.0).tm
    slots = cfg.cells_per_column * cfg.max_segments_per_cell * cfg.max_synapses_per_segment

    def pool_sized(ty):
        """Does the result type `ty` name an array of 17 x 2048 x 16,384?"""
        for dims in re.findall(r"\[(17,2048,[\d,]+)\]", ty):
            if np.prod([int(d) for d in dims.split(",")[2:]]) == slots:
                return True
        return False

    results = re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(", _scan_body(text), re.M)
    assert len(results) > 200  # a whole tick's instructions were read
    assert any(pool_sized(ty) for ty, _ in results)  # the pattern bites
    assert not [(ty, op) for ty, op in results
                if op in ("copy", "transpose") and pool_sized(ty)]
    # the moves update the pools where they lie
    assert [ty for ty, op in results if op == "fusion" and "scatter" not in ty
            and re.match(r"[sf]32\[17,2048,32,512\]\{3,2,1,0", ty)]
    # and the layout changes are the adapters', outside the loop
    entry = text[text.index("\nENTRY "):]
    moved = re.findall(r"= ([sf]32\[17,2048,[\d,]+\]\S*) copy\(", entry)
    assert len([ty for ty in moved if pool_sized(ty)]) == 4, moved


def test_nab_width_step_scatters_whole_rows_only(v5e):
    """At the NAB width the lowered step keeps its scatters — the learning
    workspace's rows moved by index — and each of them moves a window (a
    whole row), none a single element: every `stablehlo.scatter` states
    non-empty `update_window_dims`. Lowering only, in the chip's forms (the
    CPU backend's `_compact_ids` is `nonzero`, itself a scatter)."""
    from rtap_tpu.ops.step import chunk_step

    cfg = nab_preset(0.0, 100.0)
    text = chunk_step.lower(*_step_args(cfg, v5e, T=2, g=17), cfg,
                            learn=True).as_text()
    dims = re.findall(r"#stablehlo\.scatter<([^>]*)>", text)
    assert dims and len(dims) == text.count('"stablehlo.scatter"')
    for d in dims:
        assert re.search(r"update_window_dims = \[\d", d), d


@pytest.mark.parametrize("forms", ["by_shape", "by_shape_one_tick", "narrow_forced"])
def test_nab_width_chunk_step_fits_a_v5e_only_in_the_wide_row_forms(v5e, nab_chunk, forms, monkeypatch):
    """The published NAB width (2048 x 32 x 16 x 32: 16,384-lane pool rows)
    at the benchmark cell's batch of 17 streams. In the form the shape rule
    picks (tm_tpu.wide_rows: indexed row moves over [C, M, K*S] pools) the
    program fits the chip — a scan handed the public tree, and the served
    one-tick program on the resident tree a group holds; in the narrow-row
    form the cluster
    presets run — the line moved over this shape, here — it does not: the
    chip's compiler refuses it for memory, or passes it at more bytes than
    the chip has. The reason the line exists."""
    from rtap_tpu.ops.step import chunk_step

    cfg = nab_preset(0.0, 100.0)
    if forms == "by_shape":
        mem = nab_chunk.memory_analysis()
        assert "tpu_custom_call" not in nab_chunk.as_text()
        assert mem.argument_size_in_bytes >= 17 * 281_628_693
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14 * 2 ** 30
        # the carry's two kernel-layout pools (2 x 2.28 GB) stand beside the
        # donated public-layout arguments, which the outputs alias, for the
        # whole scan: 4,777,026,560 B of temporaries where the program that
        # re-laid the pools every tick took 2,744,192,512 (ISSUE 40) — what
        # a state that arrives in the kernel's layout does not pay (a stream
        # group's, ISSUE 44: under 1 GB, the resident-state test below).
        # Growth's [L, R, W] rank-match grid (1,280 x 20 x 1,280 a stream:
        # 2.2 GB if it were a buffer) still fuses into its reduce (ISSUE 28)
        assert mem.temp_size_in_bytes <= 4_777_026_560
        return
    if forms == "by_shape_one_tick":
        # the served tick's program (`StreamGroup` calls chunk_step at T = 1
        # in the live loop) on the resident tree the group holds: the
        # parameters are the carry, so neither layout of a pool stands
        # twice. (Handed the public tree, a one-tick program transposes both
        # pools in and out with both layouts live together — 13.7 GB, 8.9 of
        # them temporaries, 96.9 ms a tick on the chip, ISSUE 40; the form
        # that ran the kernel on the public layout instead, 7.72 GB and
        # 75.7 ms, went at ISSUE 50 because no owner of a state hands one.)
        compiled = chunk_step.lower(*_resident_args(cfg, v5e, 1, 17), cfg,
                                    learn=True).compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < 10 ** 9
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 5.8 * 10 ** 9
        assert re.findall(r"\[17,2048,32,512\]", compiled.as_text())
        return
    args = _step_args(cfg, v5e, T=2, g=17)
    monkeypatch.setattr(tm_tpu, "WIDE_ROW_LANES", 1 << 30)
    jax.clear_caches()  # the form is read at trace time
    try:
        assert not tm_tpu.wide_rows(cfg.tm)
        # until ISSUE 36 the compiler refused this outright (15.76 of 15.75
        # GB); with the membership test's full-pool temporaries gone it may
        # pass the program, and then says itself that the program needs more
        # than the chip holds (18.6 GB of arguments and temporaries)
        try:
            mem = chunk_step.lower(*args, cfg, learn=True).compile().memory_analysis()
        except Exception as e:  # noqa: BLE001 — the compiler's own error type
            assert re.search("RESOURCE_EXHAUSTED|[Oo]ut of memory", str(e)), e
        else:
            assert mem.argument_size_in_bytes + mem.temp_size_in_bytes > 16 * 10 ** 9
    finally:
        jax.clear_caches()


def test_serve_group_step_with_reducers_compiles_for_v5e(v5e):
    """serve's per-tick program with the in-step health and predict
    reducers armed (--health --predict), inference branch."""
    from rtap_tpu.ops.step import group_step

    cfg = cluster_preset()
    compiled = group_step.lower(*_step_args(cfg, v5e, predict=8), cfg,
                                learn=False, health=True,
                                predict=True).compile()
    # no floor on temp_size_in_bytes: since the SP reads its member bits
    # with no gather (ISSUE 26) this program's temporaries fit its outputs
    state = init_state(cfg, 0, predict_horizon=8)
    per_stream = sum(np.asarray(v).nbytes for v in state.values())
    assert compiled.memory_analysis().output_size_in_bytes >= G * per_stream


# ---- the state in the kernel's form between programs (ISSUE 44) ----

def _resident_args(cfg, sharding, T, g):
    """`_step_args` with the state's shapes in the form a `StreamGroup`
    holds on the device (ops/resident.py)."""
    state, vals, ts = _step_args(cfg, sharding, T=T, g=g)
    shapes = tm_tpu.resident_form(
        {k: np.empty(v.shape, v.dtype) for k, v in state.items()}, cfg.tm)
    return ({k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
             for k, v in shapes.items()}, vals, ts)


def _entry_moves(text: str, g: int, cfg) -> list:
    """(result type, op) of every 16- or 32-bit `copy` / `transpose` in the
    compiled program's ENTRY computation whose result is pool-sized:
    `[g, C, ...]` with K*S*M elements a column, in any of the layouts."""
    tm = cfg.tm
    slots = tm.cells_per_column * tm.max_segments_per_cell * tm.max_synapses_per_segment
    entry = text[text.index("\nENTRY "):]
    found = []
    for ty, op in re.findall(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) (copy|transpose)\(", entry, re.M):
        m = re.match(rf"[suf](?:16|32)\[{g},{cfg.sp.columns},([\d,]+)\]", ty)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) == slots:
            found.append((ty, op))
    return found


@pytest.mark.parametrize("T", [1, 2])
@pytest.mark.parametrize("preset", ["cluster", "scaled32", "node3", "nab"])
def test_resident_state_crosses_the_program_boundary_with_no_pool_copy(v5e, nab_chunk, preset, T):
    """With the state handed over in the kernel's form, the program's
    parameters and results are the scan's carry: ENTRY holds no pool-sized
    `copy` or `transpose` — at 192, 384 and 16,384 lanes a row, in the
    one-tick program the live loop calls and in a scan. The chip's default
    layout of a `[G, C, K*S*M]` argument is the one the carry wants
    (columns-minor at 192 lanes, lanes-minor at 384), so an array that
    simply has the kernel's shape arrives ready. At 32 columns the carry is
    staged into on-chip memory and back (`S(1)`), a pair of copies a pool
    each way: they stay, and are all there is. Handed the public layout the
    same programs held 6-10 such copies at the cluster and node widths
    (3.7 ms of a 21.2 ms one-tick program at 384 lanes on the chip) and four
    pool transposes at the NAB width (ISSUE 44; PERF.md §6)."""
    from rtap_tpu.ops.step import chunk_step

    cfg = {"cluster": cluster_preset, "scaled32": lambda: scaled_cluster_preset(32),
           "node3": lambda: node_preset(3),
           "nab": lambda: nab_preset(0.0, 100.0)}[preset]()
    g = 17 if preset == "nab" else G
    compiled = chunk_step.lower(*_resident_args(cfg, v5e, T, g), cfg,
                                learn=True).compile()
    text = compiled.as_text()
    if T == 2:  # the pattern bites: the public-layout program has them
        public = nab_chunk if preset == "nab" else chunk_step.lower(
            *_step_args(cfg, v5e, T=T, g=g), cfg, learn=True).compile()
        assert _entry_moves(public.as_text(), g, cfg)
    moves = _entry_moves(text, g, cfg)
    if preset == "scaled32":
        assert 0 < len(moves) <= 4 and all("S(1)" in ty or op == "copy" for ty, op in moves), moves
        staged = [ty for ty, _ in moves if "S(1)" in ty]
        assert len(staged) == len(moves) // 2, moves  # in staged, out plain
    else:
        assert not moves, moves
    if preset == "nab" and T == 2:
        # the carry IS the arguments: no second copy of the pools (4.78 GB
        # of temporaries with the public layout handed over)
        assert compiled.memory_analysis().temp_size_in_bytes < 10 ** 9
    if preset == "nab" and T == 1:
        # the one-tick program on [C, M, K*S] holds neither layout twice
        assert compiled.memory_analysis().temp_size_in_bytes < 10 ** 9
        assert re.findall(r"\[17,2048,32,512\]", text)


@pytest.mark.parametrize("preset", ["cluster", "scaled32", "node3", "nab"])
def test_public_layout_input_lowers_to_the_parents_program(v5e, preset):
    """Handed the public layout, every entry point is the program it was
    before the state could stay resident: `chunk_step` (T = 1 and 2) and
    `group_step` lower to the text of the bodies written out here — the
    adapters under `rtap.layout` around the same scan / tick, as the commit
    before ISSUE 44 had them (by sha256 against that commit's own checkout
    when the change was made: PERF.md §6, PR 44), at every program length
    since ISSUE 50 (the NAB width's one-tick programs transpose too)."""
    import rtap_tpu.ops.step as step
    from rtap_tpu.ops.tm_tpu import public_form, resident_form, tm_invariants

    cfg = {"cluster": cluster_preset, "scaled32": lambda: scaled_cluster_preset(32),
           "node3": lambda: node_preset(3),
           "nab": lambda: nab_preset(0.0, 100.0)}[preset]()

    def chunk_step(state, values, ts_unix):  # the parent's _scan_chunk
        inv = tm_invariants(cfg.tm)

        def body(s, inp):
            v, t = inp
            return step._tick(s, v, t, cfg, True, inv, health=False, predict=False)

        with jax.named_scope("rtap.layout"):
            state = resident_form(state, cfg.tm)
        state, out = jax.lax.scan(body, state, (values, ts_unix))
        with jax.named_scope("rtap.layout"):
            return public_form(state, cfg.tm), out

    def group_step(state, values, ts_unix):  # the parent's group_step
        with jax.named_scope("rtap.layout"):
            state = resident_form(state, cfg.tm)
        state, out = step._tick(state, values, ts_unix, cfg, True,
                                health=False, predict=False)
        with jax.named_scope("rtap.layout"):
            return public_form(state, cfg.tm), out

    g = 4
    for T in (1, 2, None):
        args = _step_args(cfg, v5e, T=T, g=g)
        mine = (step.group_step if T is None else step.chunk_step).lower(
            *args, cfg, learn=True).as_text()
        theirs = jax.jit(group_step if T is None else chunk_step,
                         donate_argnums=(0,)).lower(*args).as_text()
        assert mine == theirs, (preset, T)
