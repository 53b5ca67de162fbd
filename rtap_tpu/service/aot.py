"""AOT warm-up: compile every knowable serve program BEFORE tick 0.

The 1-hour 100k-stream soak (reports/live_soak_100k_1h.json) missed 9 of
3600 deadlines with latency_max 7.38 s — every one a warm-up compile
landing INSIDE a scored tick (the chunk_stagger ramp-in dispatches chunk
lengths 1..M, each a distinct XLA program, and the old warm-up keying only
serialized them). The program set is fully knowable at serve start:

  chunk lengths   1..micro_chunk (steady-state flushes at M; boundary
                  aligns, ramp-in, membership changes and the final tick
                  flush every partial length below it)
  configs         one per distinct group ModelConfig (stagger_learn gives
                  groups distinct learn_phase fields -> distinct programs)
  learn flags     the serve learn flag, plus learn=False when a
                  degradation ladder can flip scoring to frozen mid-run
  claim program   set_state_row (first dynamic slot claim / restore
                  realignment), when claimable capacity exists

so this module compiles all of them up front against a THROWAWAY state and
the loop starts with a fully warm cache; no compile can occur inside a
scored tick.

Mechanism note: jax.jit(...).lower(...).compile() builds the executable
but does NOT seed the jit dispatch cache (verified on this jax: a later
call re-traces), so warming EXECUTES each program once on scratch state —
that is the only path that guarantees the serve-loop call hits a warm
cache. The scratch state is donated through the same entry points the loop
uses (ops/step.chunk_step, ops/step.set_state_row) and freed afterwards;
group state, likelihood moments and telemetry are untouched.

Exposed metric: rtap_obs_aot_programs_compiled_total (docs/TELEMETRY.md).
Integration test: tests/integration/test_aot_serve.py pins "zero cold
compiles after tick 0" via the jit cache sizes themselves.
"""

from __future__ import annotations

from rtap_tpu.obs import get_registry
from rtap_tpu.obs.trace import span


def knowable_programs(groups, micro_chunk: int, learn: bool,
                      degradation=None) -> list[tuple]:
    """The (chunk length m, group config, learn flag) programs a serve
    loop with these parameters can ever dispatch — the same keying
    live_loop's warm-up set uses, enumerated instead of discovered."""
    learn_flags = {bool(learn)}
    if degradation is not None and learn:
        # the ladder's score_only step (level >= 2) dispatches learn=False
        learn_flags.add(False)
    cfgs = []
    for g in groups:
        if g.cfg not in cfgs:
            cfgs.append(g.cfg)
    return [
        (m, cfg, lf)
        for cfg in cfgs
        for m in range(1, max(1, int(micro_chunk)) + 1)
        for lf in sorted(learn_flags)
    ]


def prewarm(groups, micro_chunk: int, learn: bool, degradation=None,
            include_claim: bool = False, seed: int = 0,
            trace=None) -> set[tuple]:
    """Compile-and-execute every knowable program on throwaway state.

    Each program is one `rtap.aot.warm` span (obs/trace.py:SPANS) from its
    call to its result on the device — trace, compile or cache load,
    execution — recorded in `trace` (live_loop's TraceRecorder, loop track,
    name ``aot_warm``): no profiler runs this early, so the ring is how the
    launcher's time is read from inside.

    Returns the warmed key set ((m, config, learn) — live_loop seeds its
    single-flight `warmed` set with it so its own bookkeeping agrees).
    CPU-backend groups have no device programs; meshed groups compile per
    (mesh, shapes) inside sharded_chunk_step's own cache and are warmed by
    their first real dispatch — both are skipped here (the mesh path's
    fleet shapes make scratch-state warm-up a deliberate non-goal until a
    soak shows it missing deadlines).
    """
    device_groups = [g for g in groups
                     if getattr(g, "backend", None) == "tpu"
                     and getattr(g, "mesh", None) is None]
    if not device_groups:
        return set()
    import jax
    import jax.numpy as jnp

    from rtap_tpu.models.state import init_state
    from rtap_tpu.ops.resident import host_resident
    from rtap_tpu.ops.step import (
        chunk_step, replicate_state_device, set_state_row,
    )

    counter = get_registry().counter(
        "rtap_obs_aot_programs_compiled_total",
        "serve programs compiled-or-warmed ahead of tick 0 by the AOT "
        "warm-up (chunk lengths x group configs x learn flags, + claim "
        "programs; a re-warm of an already-cached program counts — the "
        "metric tracks warm-up passes, the jit cache dedupes compiles)")
    programs = knowable_programs(device_groups, micro_chunk, learn, degradation)
    warmed: set[tuple] = set()
    by_cfg: dict = {}
    for m, cfg, lf in programs:
        by_cfg.setdefault(cfg, []).append((m, lf))
    # health reducers are a static flag of the compiled program (ISSUE 6):
    # warm the variant the groups will actually dispatch, or the warm-up
    # compiles a program the loop never uses and pays the real compile
    # inside a scored tick
    health_by_cfg = {
        cfg: any(getattr(g, "health", False)
                 for g in device_groups if g.cfg == cfg)
        for cfg in by_cfg
    }
    # the predict reducer is a static flag too (ISSUE 16) AND sizes extra
    # state leaves: warm with the horizon the groups will dispatch, and
    # pass the flag EXPLICITLY — jit keys on how statics are passed, so a
    # defaulted kwarg here would compile a program the loop never reuses
    predict_by_cfg = {
        cfg: max((int(getattr(g, "predict", 0))
                  for g in device_groups if g.cfg == cfg), default=0)
        for cfg in by_cfg
    }
    for ci, (cfg, mls) in enumerate(by_cfg.items()):
        G = next(g.G for g in device_groups if g.cfg == cfg)
        pk = predict_by_cfg[cfg]
        # one scratch state per config, threaded through every program
        # (chunk_step donates its state argument, so each call consumes
        # the previous call's output buffers — no HBM accumulation), in the
        # form the groups hold theirs on the device: the programs the loop
        # will call are the ones whose state arrives resident
        fresh = host_resident(init_state(cfg, seed, predict_horizon=pk), cfg.tm)
        scratch = replicate_state_device(fresh, G)
        for m, lf in sorted(mls):
            vals = jnp.full((m, G, cfg.n_fields), jnp.nan, jnp.float32)
            ts = jnp.zeros((m, G), jnp.int32)
            with span("rtap.aot.warm", trace,
                      program=f"chunk_step.cfg{ci}.T{m}.learn{int(lf)}"):
                scratch, out = chunk_step(scratch, vals, ts, cfg, learn=lf,
                                          health=health_by_cfg[cfg],
                                          predict=bool(pk))
                jax.block_until_ready(out)
            counter.inc()
            warmed.add((m, cfg, lf))
        if include_claim:
            # the first-claim/realignment program (registry.claim_slot ->
            # set_state_row): the slot index is traced, so ONE execution
            # covers every future claim
            with span("rtap.aot.warm", trace,
                      program=f"set_state_row.cfg{ci}"):
                scratch = jax.block_until_ready(set_state_row(
                    scratch, {k: fresh[k] for k in scratch}, 0))
            counter.inc()
        del scratch
    return warmed
