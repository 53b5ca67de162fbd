"""The names the program writes into a profiler trace: `jax.named_scope`s
around the stages of the fused step (in every XLA op's `op_name`) and
`jax.profiler.TraceAnnotation`s around the phases of StreamGroup's chunk
path. The names are spelled out here on purpose — a benchmark reader matches
them in recorded traces, so a rename has to fail a test."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rtap_tpu.config import cluster_preset, node_preset, scaled_cluster_preset
from rtap_tpu.models.state import init_state
from rtap_tpu.obs.trace import span
from rtap_tpu.ops import tm_tpu
from rtap_tpu.ops.step import chunk_step, fused_step, group_step, replicate_state
from rtap_tpu.service.registry import StreamGroup

ALWAYS = ("rtap.encode", "rtap.sp.overlap", "rtap.sp.inhibit",
          "rtap.tm.activate", "rtap.tm.dendrite")
LEARNING = ("rtap.sp.learn", "rtap.tm.learn")
G, T = 2, 2


def _group_state(cfg):
    return {k: jnp.asarray(v) for k, v in
            replicate_state(init_state(cfg, 0), G).items()}


@pytest.mark.parametrize("learn", [True, False])
@pytest.mark.parametrize("cfg", [scaled_cluster_preset(32), node_preset(3)],
                         ids=["cluster32", "node3"])
def test_compiled_chunk_step_carries_the_scopes(cfg, learn):
    # sparse pools and one field; the dense SP branch under a fused
    # three-field encoder (the `node-3` deployment's program)
    hlo = chunk_step.lower(
        _group_state(cfg), jnp.zeros((T, G, cfg.n_fields), jnp.float32),
        jnp.zeros((T, G), jnp.int32), cfg, learn=learn).compile().as_text()
    # under vmap JAX wraps the outermost entry: `vmap(rtap.encode)/...`,
    # but `vmap(jit(sp_step))/rtap.sp.overlap/...`
    found = set(re.findall(r"rtap\.[a-z_.]+", " ".join(
        re.findall(r'op_name="([^"]*)"', hlo))))
    # (the layout adapters' reshapes may compile away: not required here)
    assert found - {"rtap.layout"} == set(ALWAYS + (LEARNING if learn else ()))


@pytest.mark.parametrize("rows", ["narrow", "wide"])
@pytest.mark.parametrize("entry", ["group_step", "fused_step", "chunk_step"])
def test_layout_adapters_are_scoped(entry, rows):
    """A public tree is converted under `rtap.layout` by every entry point,
    one-tick programs included: reshapes where the pools run flat (narrow
    pool rows), and a transpose a pool each way where they run [C, M, K*S]."""
    from tests.parity.test_tm_forms import form_cfg

    cfg = scaled_cluster_preset(32) if rows == "narrow" else form_cfg("wide", 16)
    assert tm_tpu.wide_rows(cfg.tm) == (rows == "wide")
    if entry == "fused_step":
        single = {k: jnp.asarray(v) for k, v in init_state(cfg, 0).items()}
        low = fused_step.lower(single, jnp.zeros((cfg.n_fields,), jnp.float32),
                               jnp.int32(0), cfg, learn=False)
    elif entry == "group_step":
        low = group_step.lower(
            _group_state(cfg), jnp.zeros((G, cfg.n_fields), jnp.float32),
            jnp.zeros((G,), jnp.int32), cfg, learn=False)
    else:
        low = chunk_step.lower(
            _group_state(cfg), jnp.zeros((T, G, cfg.n_fields), jnp.float32),
            jnp.zeros((T, G), jnp.int32), cfg, learn=False)
    text = low.as_text(debug_info=True)
    assert "rtap.layout/reshape" in text
    assert len(_ops_under(text, "transpose", "rtap.layout")) == (4 if rows == "wide" else 0)


def _ops_under(text: str, op: str, scope: str) -> list[str]:
    """The lines of a lowering (`as_text(debug_info=True)`) that hold the
    StableHLO op `op` and whose name stack — resolved through the `#loc`
    table at the end of the text — passes through `scope`."""
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    found = []
    for line in text.splitlines():
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if ref and f"stablehlo.{op}" in line and scope in locs.get(ref.group(1), ""):
            found.append(line)
    return found


@pytest.mark.parametrize("program", ["chunk_step", "chunk_step_one_tick", "group_step"])
@pytest.mark.parametrize("shape", ["cluster", "cluster32", "node3", "wide"])
def test_only_wide_rows_pay_a_transpose_at_the_layout_boundary(shape, program):
    """At narrow pool rows the kernel layout is a reshape of the public one
    and the adapters move no data: the three narrow-row presets the
    benchmark's cells run lower to no `stablehlo.transpose` under
    `rtap.layout` (ISSUE 40 left their programs the parent's, byte for
    byte). At wide rows a program handed the public tree takes the pools as
    [C, M, K*S]: two transposes in, two out, once a program, whatever its
    length (`group_step` and `chunk_step` at T = 1 as well: the kernel runs
    one layout, ISSUE 50) — and none anywhere else in the step, so no pool
    turns inside it."""
    from tests.parity.test_tm_forms import form_cfg

    cfg = {"cluster": cluster_preset, "cluster32": lambda: scaled_cluster_preset(32),
           "node3": lambda: node_preset(3), "wide": lambda: form_cfg("wide", 16)}[shape]()
    assert tm_tpu.wide_rows(cfg.tm) == (shape == "wide")
    lead = {"chunk_step": (T,), "chunk_step_one_tick": (1,), "group_step": ()}[program]
    text = (group_step if program == "group_step" else chunk_step).lower(
        _group_state(cfg), jnp.zeros((*lead, G, cfg.n_fields), jnp.float32),
        jnp.zeros((*lead, G), jnp.int32), cfg, learn=True).as_text(debug_info=True)
    turned = _ops_under(text, "transpose", "rtap.layout")
    assert _ops_under(text, "reshape", "rtap.layout")  # the resolver bites
    tm = cfg.tm
    pool = (f"{cfg.sp.columns}x{tm.max_synapses_per_segment}x"
            f"{tm.cells_per_column * tm.max_segments_per_cell}x")
    if shape == "wide":
        assert len(turned) == 4 and sum(f"-> tensor<{G}x{pool}" in ln for ln in turned) == 2
        in_step = [ln for ln in _ops_under(text, "transpose", "rtap.tm.")
                   if f"x{pool}" in ln]
        assert not in_step, in_step[:2]
    else:
        assert not turned


def test_optional_reducers_are_scoped():
    cfg = scaled_cluster_preset(32)
    text = group_step.lower(
        _group_state(cfg), jnp.zeros((G, 1), jnp.float32),
        jnp.zeros((G,), jnp.int32), cfg, learn=False,
        health=True).as_text(debug_info=True)
    assert "rtap.reduce.health/" in text


def _chunk(c):
    v = (50 + np.arange(T * G).reshape(T, G) + c).astype(np.float32)
    ts = (1_700_000_000 + c * T + np.arange(T)[:, None]
          + np.zeros((1, G))).astype(np.int64)
    return v, ts


def test_chunk_path_phases_land_in_a_profiler_trace(tmp_path):
    group = StreamGroup(scaled_cluster_preset(32), ["a0", "a1"], backend="tpu")
    group.collect_chunk(group.dispatch_chunk(*_chunk(0)))  # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        handle = group.dispatch_chunk(*_chunk(1))
        group.collect_chunk(handle)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    seen = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("rtap.group."):
                    seen[ev.name] = dict(ev.stats)
    assert sorted(seen) == ["rtap.group.enqueue", "rtap.group.fetch",
                            "rtap.group.likelihood", "rtap.group.stage"]
    assert {a["seq"] for a in seen.values()} == {handle["seq"]}
    assert {a["group"] for a in seen.values()} == {"a0"}


def test_no_trace_no_record_and_the_phases_go_through_the_seam():
    # the chunk path's phases are spans of obs/trace.py's seam (which the
    # cpu-oracle backend uses too, and which never imports JAX:
    # tests/unit/test_span_seam.py); with no profiler running one opens no
    # annotation
    group = StreamGroup(scaled_cluster_preset(32), ["a0"], backend="cpu")
    assert not hasattr(group, "_phase")
    with span("rtap.group.stage", group="a0", seq=1) as sp:
        assert sp._ann is None
