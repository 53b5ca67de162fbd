"""Run the on-hardware measurement agenda as one command on the chip.

Every step is a subprocess with its own wall budget (a hang costs one step,
not the session), ordered most-valuable-first; this parent never touches
JAX, so each step's child has the chip to itself. Send it through the chip
tool with --steps picking what fits the call's time limit. The
authoritative agenda and its ordering rationale live in the STEPS list
below (the r3 strategy matrix already measured sits first and is ledgered
done; bench + nab_corpus lead the remaining r4 agenda — see the comment
above them). --steps indices are positions in STEPS as printed by --help,
NOT a stable step id: always check the list after edits.

Logs land in chiprun_out/hw_session/<step>.log (the directory the chip tool
brings back); a one-line verdict per step prints to stderr as it completes.
Re-runs skip nothing (fresh measurements overwrite).

Usage:  python scripts/hw_session.py [--budget-per-step 600] [--steps 1,2,5]
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "chiprun_out", "hw_session")
# obs_tail imports rtap_tpu.obs in THIS process; running as `python
# scripts/hw_session.py` puts scripts/ (not the repo) at sys.path[0]
sys.path.insert(0, REPO)


def log(msg: str) -> None:
    print(f"[hw_session] {msg}", file=sys.stderr, flush=True)


# entries are (name, cmd) or (name, cmd, budget_s)
STEPS: list[tuple[str, list[str]] | tuple[str, list[str], float]] = [
    ("layout_probe", [sys.executable, "scripts/layout_probe.py"]),
    # every step pins --layout: the process default flipped to flat with the
    # r4 A/B, so an omitted flag would silently re-measure (and on a rerun
    # OVERWRITE the committed evidence logs of) a different config than the
    # step's name claims
    ("profile_matmul", [sys.executable, "scripts/profile_step.py", "--T", "32",
                        "--gs", "1024", "--layout", "aos"]),
    ("profile_indexed", [sys.executable, "scripts/profile_step.py", "--T", "32",
                         "--gs", "1024", "--layout", "aos",
                         "--scatter", "indexed"]),
    ("profile_f32_indexed", [sys.executable, "scripts/profile_step.py", "--T", "32",
                             "--gs", "1024", "--layout", "aos",
                             "--perm-bits", "0", "--scatter", "indexed"]),
    ("profile_flat", [sys.executable, "scripts/profile_step.py", "--T", "32",
                      "--gs", "1024", "--layout", "flat"]),
    ("profile_flat_indexed", [sys.executable, "scripts/profile_step.py", "--T", "32",
                              "--gs", "1024", "--layout", "flat",
                              "--scatter", "indexed"]),
    # round-4 strategies: compact punish/death sweep; forward-index dendrite
    # (both fwd histogram impls). The first silicon batch (2026-07-31;
    # docs/KERNELS.md "Measured silicon record") measured the CPU
    # "indexed wins 2.4x" signal INVERTED on TPU (indexed 18.1k vs matmul
    # 28.1k vs flat/matmul 31.9k metrics/s at G=1024), so the r4 candidates
    # are raced on the silicon winner's base (matmul scatter, aos + flat)
    # rather than the CPU-guess base (--scatter indexed) they shipped with.
    # Most-valuable-first, for a short chip budget:
    # 1. bench — the headline artifact, and its ladder already races the
    #    main candidates (flat / aos / flat+compact / flat+compact+forward)
    #    at the measured-optimal rung, so it partially subsumes the
    #    individual profiles;
    # 2. nab_corpus — the committed-artifact verdict item (minutes on
    #    silicon; the CPU fallback measured 7 s/tick and was abandoned);
    # 3. cadence profiles — validate the 100k-projection (plain chunk_step
    #    compiles, low hang risk);
    # 4. the compact/fwd profile matrix (the indexed+compact variant hung
    #    compile for its full 900 s budget once — keep these behind the
    #    high-value steps);
    # 5. sweeps and service-shape experiments.
    # Layouts explicit everywhere: the process default flipped to flat with
    # the r4 A/B, and an omitted --layout would silently duplicate configs.
    ("bench", [sys.executable, "bench.py"], 1700.0),
    ("nab_corpus", [sys.executable, "scripts/nab_standin_report.py"]),
    ("profile_cadence4", [sys.executable, "scripts/profile_step.py", "--T", "32",
                          "--gs", "1024", "--layout", "flat",
                          "--learn-every", "4"]),
    ("profile_cadence8", [sys.executable, "scripts/profile_step.py", "--T", "32",
                          "--gs", "1024", "--layout", "flat",
                          "--learn-every", "8"]),
    ("profile_flat_compact", [sys.executable, "scripts/profile_step.py", "--T", "32",
                              "--gs", "1024", "--layout", "flat",
                              "--sweep", "compact"]),
    ("profile_compact", [sys.executable, "scripts/profile_step.py", "--T", "32",
                         "--gs", "1024", "--layout", "aos",
                         "--sweep", "compact"]),
    ("profile_fwd_matmul", [sys.executable, "scripts/profile_step.py", "--T", "32",
                            "--gs", "1024", "--layout", "flat",
                            "--dendrite", "forward", "--fwd-impl", "matmul"]),
    ("profile_fwd_scatter", [sys.executable, "scripts/profile_step.py", "--T", "32",
                             "--gs", "1024", "--layout", "flat",
                             "--dendrite", "forward", "--fwd-impl", "scatter"]),
    ("profile_fwd_aos", [sys.executable, "scripts/profile_step.py", "--T", "32",
                         "--gs", "1024", "--layout", "aos",
                         "--dendrite", "forward", "--fwd-impl", "matmul"]),
    ("scaling_sweep", [sys.executable, "scripts/scaling_law.py"]),
    ("pipeline_gain", [sys.executable, "scripts/pipeline_gain.py"]),
    # round-4 service-shape experiments (verdict weak #3 / #7); the soak is
    # startup (up to ~300 s compile) + a >= 5 min paced loop by design.
    # bench above subprocess-isolates its own attempts under
    # BENCH_BUDGET_S=1500; its step budget must exceed that or the runner
    # would SIGKILL it before its own SIGTERM-emit path can print the line.
    ("multigroup", [sys.executable, "scripts/multigroup_sched.py"], 1200.0),
    # the production serve shape landed this round: many small groups per
    # chip (live_loop over a registry, interleaved dispatch). Soak it at
    # that shape — 16 x 256 streams at the 1 s-cadence north star — rather
    # than the single giant group the G-sweep already showed is the wrong
    # operating point.
    # budget sized for the 4096-stream shape: startup (<=420 s init +
    # first-tick compile) + 330 ticks at up to ~4 s/tick of degradation —
    # the soak must be able to REPORT badly missed deadlines, not get
    # SIGKILLed by its own runner while measuring them
    ("live_soak", [sys.executable, "scripts/live_soak.py",
                   "--streams", "4096", "--group-size", "256"], 2100.0),
    # The 16x256 soak measured p50 1.07 s/tick — ALL deadlines missed at
    # the 1 s cadence, ~65 ms per group per tick of dispatch+collect round
    # trip to a chip that was not host-local (the chunked multigroup throughput
    # was flat across decompositions, but live T=1 dispatches are latency-
    # bound, not bandwidth-bound). These shapes cut the round trips per
    # tick 4x/16x to isolate the per-dispatch cost from the device step.
    ("live_soak_g1024", [sys.executable, "scripts/live_soak.py",
                         "--streams", "4096", "--group-size", "1024",
                         "--out", "reports/live_soak_g1024.json"], 2100.0),
    ("live_soak_g4096", [sys.executable, "scripts/live_soak.py",
                         "--streams", "4096", "--group-size", "4096",
                         "--out", "reports/live_soak_g4096.json"], 2100.0),
    # depth-2 serve pipeline: collect tick k after dispatching k+1, hiding
    # the per-group round trip behind the cadence sleep at the production
    # 16x256 shape (alerts lag one cadence — the documented trade)
    ("live_soak_pipelined", [sys.executable, "scripts/live_soak.py",
                             "--streams", "4096", "--group-size", "256",
                             "--pipeline-depth", "2",
                             "--out", "reports/live_soak_pipelined.json"], 2100.0),
    # half-size model (scaled_cluster_preset 128 cols): measured BETTER f1
    # than the preset at half the state (reports/model_size_quality.json);
    # these measure the bandwidth-bound ~2x on silicon. The bench ladder
    # also carries the half rungs (BENCH_COLUMNS) for the headline path.
    ("profile_half", [sys.executable, "scripts/profile_step.py", "--T", "32",
                      "--gs", "1024", "--layout", "flat",
                      "--columns", "128"]),
    ("profile_half_k2", [sys.executable, "scripts/profile_step.py", "--T", "32",
                         "--gs", "1024", "--layout", "flat",
                         "--columns", "128", "--learn-every", "2"]),
    ("profile_eighth", [sys.executable, "scripts/profile_step.py", "--T", "32",
                        "--gs", "1024", "--layout", "flat",
                        "--columns", "32"]),
    # width-scaled NAB-family model over the stand-in corpus ON DEVICE
    # (minutes; the full-size run took 405 s): does the "preset is
    # oversized" finding generalize to the quality-model family on
    # diverse profiles? Scores land in reports/nab_standin_cols<N>.json,
    # never clobbering the full-size artifact.
    ("nab_cols256", [sys.executable, "scripts/nab_standin_report.py",
                     "--columns", "256"]),
    ("nab_cols512", [sys.executable, "scripts/nab_standin_report.py",
                     "--columns", "512"]),
    # first two points measured 2048 -> 8.25, 256 -> 27.69 (standard
    # profile): the width-quality curve on the corpus needs its middle and
    # lower ends before any preset recommendation is written down
    ("nab_cols128", [sys.executable, "scripts/nab_standin_report.py",
                     "--columns", "128"]),
    ("nab_cols1024", [sys.executable, "scripts/nab_standin_report.py",
                      "--columns", "1024"]),
    # small-model big-G: the full preset falls off past G=2048 (HBM-bound);
    # 64-col state is 1/4 — does the throughput curve stay flat to 8k?
    ("profile_64_g8192", [sys.executable, "scripts/profile_step.py", "--T", "32",
                          "--gs", "8192", "--layout", "flat",
                          "--columns", "64"]),
    # 32col+k2: projected ~126k/s (learning ~91% of the 32col tick) — the
    # first config past the north star whose BASE width beats the preset's
    # quality; the k=2 quality cost is measured by model_size_eval
    # (eighth_32col_k2 variant) on the CPU host
    ("profile_eighth_k2", [sys.executable, "scripts/profile_step.py", "--T", "32",
                           "--gs", "1024", "--layout", "flat",
                           "--columns", "32", "--learn-every", "2"]),
    # the 16x256 fix, round 3: depth 2 alone measured NO change (p50
    # 1.07 s — each dispatch was a blocking ~65 ms call there, so 16
    # groups serialize ~1.04 s/tick regardless of when collection
    # happens); dispatch_threads=16 overlaps the RPCs. Success = the
    # production shape holds the 1 s cadence like 4x1024 does.
    ("live_soak_threads", [sys.executable, "scripts/live_soak.py",
                           "--streams", "4096", "--group-size", "256",
                           "--pipeline-depth", "2", "--dispatch-threads", "16",
                           "--out", "reports/live_soak_threads.json"], 2100.0),
    # the headline's missing quality number: what does k=2 cost the
    # best-f1 width (0.813 detectable / 0.758 all-kinds at k=1)? At
    # 64 col, k=2 cost 8.3 points. Runs the 120x1500 protocol on-device.
    ("eval_32col_k2", [sys.executable, "scripts/model_size_eval.py",
                       "--variants", "eighth_32col_k2"]),
    ("eval_32col_k2_allkinds", [sys.executable, "scripts/model_size_eval.py",
                                "--variants", "eighth_32col_k2",
                                "--all-kinds"]),
    # resident-capability frontier at the headline width: 256-col OOMs
    # between 8k and 16k streams/chip; 32-col state is 1/8, so the
    # frontier should land ~64k-128k — if >= 100k streams FIT and score
    # on ONE chip, the "100k-on-one-chip unreachable" r3 verdict flips
    # on the width axis. profile_step records FAILED per-G and exits 0,
    # so the OOM probe cannot burn watcher attempts.
    ("profile_32col_bigg", [sys.executable, "scripts/profile_step.py",
                            "--T", "32", "--gs", "16384", "32768", "65536",
                            "98304", "131072", "--layout", "flat",
                            "--columns", "32"], 1800.0),
    # absolute ceiling probe: u8 perm domain halves state again
    # (quality per domain measured in SCALING.md's domain table)
    ("profile_32col_bigg_u8", [sys.executable, "scripts/profile_step.py",
                               "--T", "32", "--gs", "131072", "196608",
                               "262144", "--layout", "flat", "--columns", "32",
                               "--perm-bits", "8"], 1800.0),
    # quality numbers for the u8-domain capability configs (16 s each on
    # device at the 120x1500 protocol)
    ("eval_32col_u8", [sys.executable, "scripts/model_size_eval.py",
                       "--variants", "eighth_32col_u8,eighth_32col_u8_k2"]),
    ("eval_32col_u8_allkinds", [sys.executable, "scripts/model_size_eval.py",
                                "--variants",
                                "eighth_32col_u8,eighth_32col_u8_k2",
                                "--all-kinds"]),
    # live capability at the measured resident frontier: 16k and 32k
    # streams at 1 s cadence WITH learning on one chip (32col learn
    # ticks profile 345/769 ms at G=16k/32k; k=2 + depth 2 + threads
    # hide the rest). Startup pays a big state transfer: raised budget.
    ("live_soak_16k", [sys.executable, "scripts/live_soak.py",
                       "--streams", "16384", "--group-size", "4096",
                       "--columns", "32", "--learn-every", "2",
                       "--pipeline-depth", "2", "--dispatch-threads", "4",
                       "--startup-timeout", "900",
                       "--out", "reports/live_soak_16k.json"], 2400.0),
    ("live_soak_32k", [sys.executable, "scripts/live_soak.py",
                       "--streams", "32768", "--group-size", "4096",
                       "--columns", "32", "--learn-every", "2",
                       "--pipeline-depth", "2", "--dispatch-threads", "8",
                       "--startup-timeout", "900",
                       "--out", "reports/live_soak_32k.json"], 2400.0),
    # frozen serving at the FULL resident frontier: inference-only ticks
    # profile ~1/5 of learning, so 65,536 frozen streams should hold 1 s
    # where learning cannot (1,555 ms/tick). Capability-envelope probe: a
    # fresh model served frozen measures the serving path, not detection.
    ("live_soak_64k_frozen", [sys.executable, "scripts/live_soak.py",
                              "--streams", "65536", "--group-size", "8192",
                              "--columns", "32", "--freeze",
                              "--pipeline-depth", "2",
                              "--dispatch-threads", "8",
                              "--startup-timeout", "1200",
                              "--out", "reports/live_soak_64k_frozen.json"],
     2700.0),
    # width x probation composition: does the lp600 likelihood lever
    # (+3 points on the preset) stack with the 32col width (0.813)?
    ("eval_32col_lp600", [sys.executable, "scripts/model_size_eval.py",
                          "--variants",
                          "eighth_32col_lp600,eighth_32col_k2_lp600"]),
    ("eval_32col_lp600_allkinds", [sys.executable,
                                   "scripts/model_size_eval.py",
                                   "--variants",
                                   "eighth_32col_lp600,eighth_32col_k2_lp600",
                                   "--all-kinds"]),
    # dynamic slot claim on the real chip: set_state_row's donated
    # .at[slot].set against grouped TPU state + scoring continuity
    ("dynamic_claim", [sys.executable, "scripts/dynamic_claim_probe.py"]),
    # elastic churn under deadline at production scale: does a mid-soak
    # claim/release (drain-first membership rule + on-device row reset)
    # cost missed ticks? ~16 rotations over the 330-tick soak.
    ("live_soak_churn", [sys.executable, "scripts/live_soak.py",
                         "--streams", "4096", "--group-size", "1024",
                         "--columns", "32", "--learn-every", "2",
                         "--pipeline-depth", "2", "--dispatch-threads", "4",
                         "--churn-every", "20", "--startup-timeout", "900",
                         "--out", "reports/live_soak_churn.json"], 2400.0),
    # sustained stability: 30 minutes of continuous churn at the
    # production shape — memory leaks, counter drift, or slow latency
    # creep would surface here, not in a 5-minute soak
    ("live_soak_30min", [sys.executable, "scripts/live_soak.py",
                         "--streams", "4096", "--group-size", "1024",
                         "--columns", "32", "--learn-every", "2",
                         "--pipeline-depth", "2", "--dispatch-threads", "4",
                         "--churn-every", "30", "--ticks", "1800",
                         "--startup-timeout", "900",
                         "--out", "reports/live_soak_30min.json"], 3300.0),
    # disambiguate the >65k resident wall: u16 fails at 98304; if u8 at
    # 81920/98304 also fails, the wall is purely G-structural in the
    # remote compiler (no state-size component)
    ("profile_32col_u8_mid", [sys.executable, "scripts/profile_step.py",
                              "--T", "32", "--gs", "81920", "98304",
                              "--layout", "flat", "--columns", "32",
                              "--perm-bits", "8"], 1800.0),
    # ---------------- round 5 ----------------
    # Pallas re-race at the HEADLINE width (verdict r4 item 8): the dendrite
    # kernel lost at 256-col/aos (24.3k vs 31.9k); arithmetic intensity at
    # 32-col/flat is different. A/B at the exact headline config (k=2) and
    # its full-rate base.
    # The >65k wall is per-program workspace, which scales with G AND the
    # scan chunk T (verdict r4 item 2: "smaller scan T at scale"). If T=8
    # compiles at 98304 where T=32 500s, the wall is the T-scaled feed/
    # workspace, and single-program residency extends toward 100k. T=8 at
    # 65536 calibrates the T-cost at a known-good G first.
    ("r5_T8_65k", [sys.executable, "scripts/profile_step.py",
                   "--T", "8", "--gs", "65536", "--layout", "flat",
                   "--columns", "32"], 1500.0),
    ("r5_T8_98k", [sys.executable, "scripts/profile_step.py",
                   "--T", "8", "--gs", "98304", "131072", "--layout", "flat",
                   "--columns", "32"], 1800.0),
    # THE round-5 flagship (verdict item 2): 100k streams LIVE LEARNING at
    # 1 s on ONE chip. The >65k single-program wall is G-structural
    # (r5_T8_98k: T=8 still compile-500s), so the route is many small
    # groups — the shape live serving already prefers (SCALING.md: compute
    # throughput PEAKS at G~1024; the 32k soak at 8x4096 held p50 67 ms
    # with 15x headroom). 100x1024 at 32col/k=2: average device compute
    # ~102400/136k = 0.75 s/tick, spread evenly by --stagger-learn so no
    # single tick carries the whole fleet's learning spike; 16 threads
    # overlap the ~65 ms/group dispatch RPCs.
    ("r5_soak_100k", [sys.executable, "scripts/live_soak.py",
                      "--streams", "102400", "--group-size", "1024",
                      "--columns", "32", "--learn-every", "2",
                      "--stagger-learn", "--pipeline-depth", "2",
                      "--dispatch-threads", "16",
                      "--startup-timeout", "1800",
                      "--out", "reports/live_soak_100k.json"], 4200.0),
    # 65,536 LEARNING live (r4 only demonstrated 65k frozen / 32k learning):
    # the intermediate capability rung, and the control for the 100-group
    # RPC-overhead question (16 groups here).
    ("r5_soak_64k_learn", [sys.executable, "scripts/live_soak.py",
                           "--streams", "65536", "--group-size", "4096",
                           "--columns", "32", "--learn-every", "2",
                           "--stagger-learn", "--pipeline-depth", "2",
                           "--dispatch-threads", "8",
                           "--startup-timeout", "1500",
                           "--out", "reports/live_soak_64k_learn.json"], 3600.0),
    # alternate 100k shape (25x4096): fewer, bigger dispatches — wins if
    # the 100-group RPC wall dominates, loses if the per-G compute falloff
    # (47k/s at G=16384 vs 74k at G=1024, k=1) dominates.
    ("r5_soak_100k_g4096", [sys.executable, "scripts/live_soak.py",
                            "--streams", "102400", "--group-size", "4096",
                            "--columns", "32", "--learn-every", "2",
                            "--stagger-learn", "--pipeline-depth", "2",
                            "--dispatch-threads", "8",
                            "--startup-timeout", "1800",
                            "--out", "reports/live_soak_100k_g4096.json"],
     4200.0),
    # pinned full-rate trend rung (verdict item 4): novel vs repeated feed
    # at the full preset, G=256/T=64 — explains r3 38,956 -> r4 32,904
    ("r5_trend_rung", [sys.executable, "scripts/trend_rung.py"], 1500.0),
    # roofline/MFU accounting (verdict item 3): XLA cost_analysis of the
    # TPU-lowered step vs chip peaks vs the committed measured times
    ("r5_roofline", [sys.executable, "scripts/roofline.py"], 1800.0),
    # held-out external validation of the width ladder (verdict item 1):
    # 7 variants x 3 seeds x 3 magnitudes, all 5 kinds, 120x1500 each.
    # Incremental-merge into reports/heldout_eval.json — a window drop
    # resumes where it left off.
    ("r5_heldout_eval", [sys.executable, "scripts/heldout_eval.py"], 5400.0),
    # 100k-soak forensics: the tick period pinned at ~1.4 s at BOTH
    # 100x1024/102k and 16x4096/64k (and 2.19 s at 25x4096/102k) — the
    # instrumented live_loop now reports phase_ms_per_tick
    # (source/dispatch/collect/emit); this rerun names the binding phase.
    ("r5_soak_64k_phase", [sys.executable, "scripts/live_soak.py",
                           "--streams", "65536", "--group-size", "4096",
                           "--columns", "32", "--learn-every", "2",
                           "--stagger-learn", "--pipeline-depth", "2",
                           "--dispatch-threads", "8",
                           "--startup-timeout", "1500",
                           "--out", "reports/live_soak_64k_phase.json"],
     3600.0),
    # the cadence ladder's hold candidate: k=4 halves the per-tick device
    # compute vs k=2 (learning is ~9x an inference tick at 32col) — at
    # 100x1024 the projection is ~0.8 s/tick. Quality cost measured by
    # r5_eval_k4/r5_heldout_eval, never assumed.
    ("r5_soak_100k_k4", [sys.executable, "scripts/live_soak.py",
                         "--streams", "102400", "--group-size", "1024",
                         "--columns", "32", "--learn-every", "4",
                         "--stagger-learn", "--pipeline-depth", "2",
                         "--dispatch-threads", "16",
                         "--startup-timeout", "1800",
                         "--out", "reports/live_soak_100k_k4.json"], 4200.0),
    # diurnal-family quality for the cadence ladder (same protocol as the
    # committed model_size artifacts; heldout covers the other family)
    ("r5_eval_k4", [sys.executable, "scripts/model_size_eval.py",
                    "--variants", "eighth_32col_k3,eighth_32col_k4"]),
    ("r5_eval_k4_allkinds", [sys.executable, "scripts/model_size_eval.py",
                             "--variants", "eighth_32col_k3,eighth_32col_k4",
                             "--all-kinds"]),
    # fresh headline for the round (the driver also runs bench.py itself
    # at round end)
    ("r5_bench", [sys.executable, "bench.py"], 1700.0),
    # 100k cadence, round 3 of forensics: k=4 changed NOTHING (p50 1392 vs
    # 1398 ms) — at 100x1024 the binder is ~200 blocking ~70 ms RPCs/tick
    # 16-way overlapped (~0.9 s wall), not device compute. RPC waits
    # release the GIL; 48 threads project the RPC wall to ~0.3 s. k=2
    # first (the better-quality operating point).
    ("r5_soak_100k_t48", [sys.executable, "scripts/live_soak.py",
                          "--streams", "102400", "--group-size", "1024",
                          "--columns", "32", "--learn-every", "2",
                          "--stagger-learn", "--pipeline-depth", "2",
                          "--dispatch-threads", "48",
                          "--startup-timeout", "1800",
                          "--out", "reports/live_soak_100k_t48.json"],
     4200.0),
    ("r5_soak_100k_k4_t48", [sys.executable, "scripts/live_soak.py",
                             "--streams", "102400", "--group-size", "1024",
                             "--columns", "32", "--learn-every", "4",
                             "--stagger-learn", "--pipeline-depth", "2",
                             "--dispatch-threads", "48",
                             "--startup-timeout", "1800",
                             "--out",
                             "reports/live_soak_100k_k4_t48.json"], 4200.0),
    # Micro-chunk ladder: the per-program invocation floor (~6-12 ms,
    # thread- and cadence-invariant — r5 forensics) divides by M when M
    # ticks ride one dispatch (live_loop micro_chunk; bit-exact vs
    # per-tick by test). Price: <= (2M-1) ticks alert staleness at depth
    # 2. k=2 kept where possible (better quality: heldout 0.4002 vs k4
    # 0.3945, diurnal 0.762 vs 0.739).
    ("r5_soak_100k_m2", [sys.executable, "scripts/live_soak.py",
                         "--streams", "102400", "--group-size", "1024",
                         "--columns", "32", "--learn-every", "2",
                         "--stagger-learn", "--micro-chunk", "2",
                         "--pipeline-depth", "2", "--dispatch-threads", "16",
                         "--startup-timeout", "1800",
                         "--out", "reports/live_soak_100k_m2.json"], 4200.0),
    ("r5_soak_100k_m4", [sys.executable, "scripts/live_soak.py",
                         "--streams", "102400", "--group-size", "1024",
                         "--columns", "32", "--learn-every", "2",
                         "--stagger-learn", "--micro-chunk", "4",
                         "--pipeline-depth", "2", "--dispatch-threads", "16",
                         "--startup-timeout", "1800",
                         "--out", "reports/live_soak_100k_m4.json"], 4200.0),
    ("r5_soak_100k_k4_m4", [sys.executable, "scripts/live_soak.py",
                            "--streams", "102400", "--group-size", "1024",
                            "--columns", "32", "--learn-every", "4",
                            "--stagger-learn", "--micro-chunk", "4",
                            "--pipeline-depth", "2",
                            "--dispatch-threads", "16",
                            "--startup-timeout", "1800",
                            "--out",
                            "reports/live_soak_100k_k4_m4.json"], 4200.0),
    # THE steady-state capability soaks. Every soak above unknowingly ran
    # the 300-tick FULL-RATE maturity window over 91% of its 330 ticks
    # (serve's with_learn_every default) — which is why k/threads/m never
    # moved the needle. --learn-full-until 0 measures the mature fleet
    # (profile/bench semantics; production onboards gradually and never
    # pays the whole window at once). k4+m4 projects ~0.65 s/tick; k2+m4
    # ~1.0 s (marginal, better quality) — measure both.
    ("r5_soak_100k_steady_k4m4", [sys.executable, "scripts/live_soak.py",
                                  "--streams", "102400", "--group-size",
                                  "1024", "--columns", "32",
                                  "--learn-every", "4", "--learn-full-until",
                                  "0", "--stagger-learn", "--micro-chunk",
                                  "4", "--pipeline-depth", "2",
                                  "--dispatch-threads", "16",
                                  "--startup-timeout", "1800",
                                  "--out",
                                  "reports/live_soak_100k_steady_k4m4.json"],
     4200.0),
    ("r5_soak_100k_steady_k2m4", [sys.executable, "scripts/live_soak.py",
                                  "--streams", "102400", "--group-size",
                                  "1024", "--columns", "32",
                                  "--learn-every", "2", "--learn-full-until",
                                  "0", "--stagger-learn", "--micro-chunk",
                                  "4", "--pipeline-depth", "2",
                                  "--dispatch-threads", "16",
                                  "--startup-timeout", "1800",
                                  "--out",
                                  "reports/live_soak_100k_steady_k2m4.json"],
     4200.0),
    # THE capability soak: chunk_stagger levels the boundary spike (the
    # steady k4m4 run was sustainable at ~0.7 s/tick average but carried
    # 2.8 s of chunk work on every 4th tick = 83 guaranteed misses). With
    # rotated boundaries each tick carries ~25 groups' dispatch+collect —
    # projection ~0.7 s/tick EVERY tick. Bit-exact vs plain serving by
    # test (tests/unit/test_multigroup_serve.py).
    ("r5_soak_100k_final", [sys.executable, "scripts/live_soak.py",
                            "--streams", "102400", "--group-size", "1024",
                            "--columns", "32", "--learn-every", "4",
                            "--learn-full-until", "0", "--stagger-learn",
                            "--micro-chunk", "4", "--chunk-stagger",
                            "--pipeline-depth", "2",
                            "--dispatch-threads", "16",
                            "--startup-timeout", "1800",
                            "--out", "reports/live_soak_100k_final.json"],
     4200.0),
    # quality-better operating point at the same per-tick budget: k=3
    # (diurnal f1 0.7499 vs k4's 0.7389) with m=6 boundaries
    ("r5_soak_100k_final_k3m6", [sys.executable, "scripts/live_soak.py",
                                 "--streams", "102400", "--group-size",
                                 "1024", "--columns", "32",
                                 "--learn-every", "3", "--learn-full-until",
                                 "0", "--stagger-learn", "--micro-chunk",
                                 "6", "--chunk-stagger",
                                 "--pipeline-depth", "2",
                                 "--dispatch-threads", "16",
                                 "--startup-timeout", "1800",
                                 "--out",
                                 "reports/live_soak_100k_k3m6.json"],
     4200.0),
    # f32 permanence domain at the headline width (roofline follow-up):
    # the u16 storage the presets default to charges decode/encode
    # conversion passes over the largest pools EVERY tick; f32 skips them
    # at ~1.4x the state (still ~10 GB at 100k streams — fits). If it
    # wins, it is a free throughput bump at reference-faithful semantics.
    ("r5_f32_32col", [sys.executable, "scripts/profile_step.py",
                      "--T", "32", "--gs", "1024", "--layout", "flat",
                      "--columns", "32", "--perm-bits", "0"]),
    ("r5_f32_32col_k4", [sys.executable, "scripts/profile_step.py",
                         "--T", "32", "--gs", "1024", "--layout", "flat",
                         "--columns", "32", "--perm-bits", "0",
                         "--learn-every", "4"]),
    ("r5_f32_preset", [sys.executable, "scripts/profile_step.py",
                       "--T", "32", "--gs", "1024", "--layout", "flat",
                       "--perm-bits", "0"]),
    # complete the held-out ladder with the k=3 serving operating point
    # (merges into the existing artifact; ~1 min on device)
    ("r5_heldout_k3", [sys.executable, "scripts/heldout_eval.py",
                       "--variants", "eighth_32col_k3"]),
    # refresh the NAB stand-in artifact under the EXHAUSTIVE sweeper (the
    # committed scores were produced by the old ~200-quantile sweep; the
    # exhaustive optimum can only be >=, and the artifact must match the
    # shipped scorer)
    ("r5_nab_exhaustive", [sys.executable,
                           "scripts/nab_standin_report.py"], 1200.0),
    # width-curve points refreshed under the exhaustive sweeper (artifact
    # consistency with the shipped scorer; the full-size refresh moved
    # 8.25 -> 11.89 standard)
    ("r5_nab256", [sys.executable, "scripts/nab_standin_report.py",
                   "--columns", "256"]),
    ("r5_nab512", [sys.executable, "scripts/nab_standin_report.py",
                   "--columns", "512"]),
    # endurance at the flagship point: 30 MINUTES of 102,400 live
    # learning streams at the k3/m6 steady state — leaks, drift, or
    # latency creep would surface here, not in a 5.5-minute soak
    ("r5_soak_100k_30min", [sys.executable, "scripts/live_soak.py",
                            "--streams", "102400", "--group-size", "1024",
                            "--columns", "32", "--learn-every", "3",
                            "--learn-full-until", "0", "--stagger-learn",
                            "--micro-chunk", "6", "--chunk-stagger",
                            "--ticks", "1800", "--pipeline-depth", "2",
                            "--dispatch-threads", "16",
                            "--startup-timeout", "1800",
                            "--out",
                            "reports/live_soak_100k_30min.json"], 4500.0),
    # the quality-tier live point: 128col measured the BEST held-out f1
    # (0.4447 vs preset 0.4033); this soak backs docs/DEPLOYMENT.md's
    # 128col row with a live capability artifact at 16k streams
    ("r5_soak_16k_128col", [sys.executable, "scripts/live_soak.py",
                            "--streams", "16384", "--group-size", "1024",
                            "--columns", "128", "--learn-every", "2",
                            "--learn-full-until", "0", "--stagger-learn",
                            "--micro-chunk", "4", "--chunk-stagger",
                            "--pipeline-depth", "2",
                            "--dispatch-threads", "16",
                            "--startup-timeout", "1500",
                            "--out",
                            "reports/live_soak_16k_128col.json"], 3000.0),
    # capstone: elastic churn AT the flagship scale — 102,400 streams
    # with a stream rotating out (auto-released) and a new id in
    # (auto-registered) every 30 s, under the full serving stack
    # (k3/m6/chunk-stagger; membership forces warm boundary realignments)
    ("r5_soak_100k_churn", [sys.executable, "scripts/live_soak.py",
                            "--streams", "102400", "--group-size", "1024",
                            "--columns", "32", "--learn-every", "3",
                            "--learn-full-until", "0", "--stagger-learn",
                            "--micro-chunk", "6", "--chunk-stagger",
                            "--churn-every", "30", "--pipeline-depth", "2",
                            "--dispatch-threads", "16",
                            "--startup-timeout", "1800",
                            "--out",
                            "reports/live_soak_100k_churn.json"], 4200.0),
    # tighter error bars on the held-out verdict: two more seeds over the
    # full variant ladder (merge-incremental; ~5 s/cell on device)
    ("r5_heldout_seeds2", [sys.executable, "scripts/heldout_eval.py",
                           "--seeds", "59,71"], 2400.0),
    # the hour-long flagship run: 3600 ticks of 102,400 live learning
    # streams at the k3/m6 point — 368M+ metrics in one unbroken serve
    ("r5_soak_100k_1h", [sys.executable, "scripts/live_soak.py",
                         "--streams", "102400", "--group-size", "1024",
                         "--columns", "32", "--learn-every", "3",
                         "--learn-full-until", "0", "--stagger-learn",
                         "--micro-chunk", "6", "--chunk-stagger",
                         "--ticks", "3600", "--pipeline-depth", "2",
                         "--dispatch-threads", "16",
                         "--startup-timeout", "1800",
                         "--out",
                         "reports/live_soak_100k_1h.json"], 6600.0),
    # lifecycle honesty: 900 ticks under the DEFAULT maturity window —
    # the cold-start fleet pays ~300 full-rate ticks (misses expected),
    # then the cadenced steady state must hold; production onboards
    # gradually and never pays the whole window at once
    ("r5_soak_100k_lifecycle", [sys.executable, "scripts/live_soak.py",
                                "--streams", "102400", "--group-size",
                                "1024", "--columns", "32",
                                "--learn-every", "4", "--stagger-learn",
                                "--micro-chunk", "4", "--chunk-stagger",
                                "--ticks", "900", "--pipeline-depth", "2",
                                "--dispatch-threads", "16",
                                "--startup-timeout", "1800",
                                "--out",
                                "reports/live_soak_100k_lifecycle.json"],
     3600.0),
    # ---------------- round 6 (ISSUE 3: close the latency-bound gap) ----
    # Most-valuable-first: (1) the silicon profile_r06 — re-measures the
    # full-rate number UNDER the fused-region consolidation and commits
    # the per-region HLO extraction the round's analysis cites (this run
    # OVERWRITES reports/profile_r06.json, replacing the CPU-labeled
    # stand-in artifact with silicon — exactly the intended upgrade);
    # (2) the megakernel A/B at the preset width and the headline width
    # (RTAP_TM_SCATTER=pallas; a Mosaic compile failure or VMEM overrun is
    # a MEASURED negative result — the step log is the evidence either
    # way, same protocol as the r4 candidates); (3) a fresh bench, whose
    # ladder now carries the pallas rung and appends the full-rate trend
    # entry to reports/trend_rung.json.
    ("profile_r06", [sys.executable, "scripts/profile_step.py", "--T", "32",
                     "--gs", "1024", "--layout", "flat",
                     "--report", "reports/profile_r06.json"], 1500.0),
    ("profile_mega", [sys.executable, "scripts/profile_step.py", "--T", "32",
                      "--gs", "1024", "--layout", "flat",
                      "--scatter", "pallas"], 1500.0),
    ("profile_mega_32col", [sys.executable, "scripts/profile_step.py",
                            "--T", "32", "--gs", "1024", "--layout", "flat",
                            "--columns", "32", "--scatter", "pallas"],
     1500.0),
    ("r6_bench", [sys.executable, "bench.py"], 1700.0),
    ("r6_trend_rung", [sys.executable, "scripts/trend_rung.py"], 1500.0),
    # ---------------- round 7 (ISSUE 4: tracing + flight recorder) ----
    # Paired host+device timelines of the SAME 100-tick serve window at
    # the production multi-group shape: jax.profiler.trace captures the
    # XLA device trace (TensorBoard/Perfetto-loadable, under
    # chiprun_out/hw_session/device_trace_r07/) while serve's span recorder writes
    # the host timeline (chiprun_out/hw_session/host_trace_r07.json) — the first
    # artifact that can attribute a missed tick to device compute vs the
    # dispatch RPC wall vs host phases on silicon. The flight recorder
    # flies armed so any quarantine/miss-burst during the window leaves
    # a bundle next to the traces. 100 ticks keeps the device trace file
    # small enough to commit; budget covers init + warm-up + the window.
    ("r7_device_trace", [sys.executable, "scripts/live_soak.py",
                         "--streams", "4096", "--group-size", "1024",
                         "--columns", "32", "--learn-every", "2",
                         "--stagger-learn", "--ticks", "100",
                         "--pipeline-depth", "2", "--dispatch-threads", "4",
                         "--jax-trace", "chiprun_out/hw_session/device_trace_r07",
                         "--trace-out", "chiprun_out/hw_session/host_trace_r07.json",
                         "--postmortem-dir", "chiprun_out/hw_session/postmortems_r07",
                         "--startup-timeout", "900",
                         "--out", "reports/live_soak_trace_r07.json"],
     2400.0),
    # ---------------- round 8 (ISSUE 5: crash-consistent durability) ----
    # Real-clock supervised kill-9 soak at the production shape: a
    # journaled + checkpointed serve child over the seeded feed is
    # SIGKILLed 10 times at journal-observed ticks and restarted by the
    # real Supervisor; the verdict (exit 5 on failure) is final model
    # state bit-identical to the fault-free run and the concatenated
    # alert stream exactly-once (zero duplicated / zero lost alert_ids).
    # The committed report carries the silicon catch-up numbers the docs
    # cite: per-restart journal replay ticks + wall seconds (how long a
    # crashed chip takes to be back at the live edge) and the torn-tail
    # truncation count. 600 ticks at 1 s cadence ~ 10 min fault-free;
    # the budget covers the reference run + 10 restart cycles, each
    # paying jax init + compile-cache-warm startup on top of replay.
    ("r8_crash_soak", [sys.executable, "scripts/crash_soak.py",
                       "--seed", "8", "--kills", "10",
                       "--streams", "4096", "--group-size", "1024",
                       "--ticks", "600", "--cadence", "1.0",
                       "--checkpoint-every", "60", "--backend", "tpu",
                       "--threshold", "0.5", "--journal-fsync", "every-64",
                       "--out", "reports/crash_soak_r08.json"],
     3600.0),
    # ---------------- round 9 (ISSUE 6: model-health observability) ----
    # The health-reducer silicon numbers the docs cite: the same
    # 4096x1024 production soak shape as r7, with the fused on-device
    # health reducers armed. Evidence harvested from the run's obs
    # snapshot + stats line: (1) OVERHEAD — tick latency percentiles and
    # missed-deadline count vs the r7 baseline quantify what the ~200 B/
    # group/tick reducer pass costs inside the compiled step (the CPU
    # path is proven bit-exact and <= 1%-host-fold in tier-1; the
    # device-side region cost only silicon can price); (2) OCCUPANCY —
    # the fleet's real segment-pool occupancy histogram at steady state,
    # the first measured input to ROADMAP-3 pool right-sizing. The
    # flight recorder flies armed so any pool_saturated/score_drift
    # incident during the window leaves a bundle with the scorecard
    # embedded.
    ("r9_health", [sys.executable, "scripts/live_soak.py",
                   "--streams", "4096", "--group-size", "1024",
                   "--columns", "32", "--learn-every", "2",
                   "--stagger-learn", "--ticks", "300",
                   "--pipeline-depth", "2", "--dispatch-threads", "4",
                   "--health",
                   "--postmortem-dir", "chiprun_out/hw_session/postmortems_r09",
                   "--startup-timeout", "900",
                   "--out", "reports/live_soak_health_r09.json"],
     2400.0),
    # ---------------- round 10 (ISSUE 7: wire-speed binary ingest) -----
    # Silicon soak at the new ingest ceiling: the same 4096x1024
    # production shape as r9, fed through serve --ingest-port (RB1
    # binary batch frames, one vectorized frame per feeder tick — the
    # host-side ingest edge that bounded the 100k soak at ~102k
    # metrics/s is off the critical path; reports/ingest_r07.json holds
    # the host-only microbench: >=5x the JSONL TCP path, multi-M
    # rows/s). Health + flight armed like r9 so the run doubles as the
    # regression baseline for both; the artifact's ingest counters
    # (frames/rows/garbage/backpressure, snapshot rtap_obs_ingest_*)
    # say data flowed clean at cadence on silicon.
    ("r10_ingest", [sys.executable, "scripts/live_soak.py",
                    "--binary-ingest",
                    "--streams", "4096", "--group-size", "1024",
                    "--columns", "32", "--learn-every", "2",
                    "--stagger-learn", "--ticks", "300",
                    "--pipeline-depth", "2", "--dispatch-threads", "4",
                    "--health",
                    "--postmortem-dir", "chiprun_out/hw_session/postmortems_r10",
                    "--startup-timeout", "900",
                    "--out", "reports/live_soak_ingest_r10.json"],
     2400.0),
    # ---------------- round 11 (ISSUE 8: hot-standby failover) --------
    # Real-clock failover soak at production cadence on the silicon
    # host (the PAIR is cpu-oracle here — two serve processes cannot
    # share the one chip; the device-mesh pair is ROADMAP-1's follow-
    # up): 2 SIGKILLs of the live leader + the SIGSTOP fence round at
    # 1 s cadence with a 5 s lease. The committed report carries the
    # real-host takeover numbers the runbook cites: per-takeover
    # detect_ticks (budget <= 10), promotion splice sizes
    # (re_emitted/suppressed), and the fenced zombie's refused-write
    # count. Budget covers the reference run + the HA run with three
    # restart cycles at 1 s ticks.
    ("r11_failover", [sys.executable, "scripts/failover_soak.py",
                      "--seed", "8", "--kills", "2",
                      "--streams", "96", "--group-size", "32",
                      "--ticks", "420", "--cadence", "1.0",
                      "--checkpoint-every", "30", "--backend", "cpu",
                      "--lease-timeout", "5.0",
                      "--out", "reports/failover_soak_r11.json"],
     3600.0),
    # ---------------- round 12 (ISSUE 9: workload breadth) ------------
    # The composite multi-field encoder on silicon with incident
    # correlation armed: the seeded cascading-fault soak (exactly ONE
    # cluster-level incident, kill-9 identical incident stream, bit-
    # identical state) at a 4-service topology, scored through the
    # {value, delta, event-class} fused-SDR device encoder. What only
    # silicon can price: the per-field encode kernels (three disjoint
    # layout segments vs one uniform RDSE) inside the compiled step at
    # real cadence, and the correlator fold riding the 1 s tick on the
    # hw host. --threshold 0.04 is the composite contrast point (the
    # fused SDR spreads novelty over three fields, flattening the
    # likelihood profile; see workload_soak --threshold help; cpu-
    # measured burst ~0.07-0.09 vs healthy ~0.02). Budget covers the
    # reference + crash runs at 1 s ticks plus compile.
    ("r12_workloads", [sys.executable, "scripts/workload_soak.py",
                       "--seed", "9", "--kills", "2",
                       "--preset", "composite", "--threshold", "0.04",
                       "--services", "4", "--nodes-per-service", "4",
                       "--group-size", "16", "--ticks", "420",
                       "--cadence", "1.0", "--checkpoint-every", "30",
                       "--backend", "tpu",
                       "--out", "reports/workload_soak_r12.json"],
     3600.0),
    # ---------------- round 13 (ISSUE 11: detection-latency SLOs) -----
    # The real-time headline on silicon: the r9/r10 production soak
    # shape with detection-latency tracking + declared SLOs armed. The
    # live feeder stamps rows with the host wall clock, so the e2e
    # detect sketch (source ts -> alert-sink flush) is the TRUE
    # detection latency of the served fleet at 1 s cadence — the first
    # measured number behind ROADMAP-2's "sub-second detection" premium
    # tier (the fault-eval's median 1-2 s is model latency; this is the
    # whole pipeline). detect=2s@p99 is the launch contract, tick=1s@p99
    # the cadence contract; a burn dumps a postmortem whose summary
    # embeds the waterfall, and the committed report carries the full
    # per-stage quantiles + the SLO verdict. --threshold 0.35 densifies
    # alert traffic enough to fill the detect sketch without drowning
    # the sink (cpu-measured alert rate at the sine feed).
    ("r13_latency", [sys.executable, "scripts/live_soak.py",
                     "--streams", "4096", "--group-size", "1024",
                     "--columns", "32", "--learn-every", "2",
                     "--stagger-learn", "--ticks", "300",
                     "--pipeline-depth", "2", "--dispatch-threads", "4",
                     "--threshold", "0.35",
                     "--latency", "--slo", "detect=2s@p99",
                     "--slo", "tick=1s@p99",
                     "--postmortem-dir", "chiprun_out/hw_session/postmortems_r13",
                     "--startup-timeout", "900",
                     "--out", "reports/live_soak_latency_r13.json"],
     2400.0),
    # ---------------- round 15 (ISSUE 16: predictive horizon) ---------
    # The title claim on silicon: the committed cpu cascade gate
    # (reports/predict_r15.json — precursor ramp at the origin node,
    # lagged step faults downstream, win = page BEFORE the second
    # node's onset with the blast radius covered and zero false
    # precursors) re-measured with the predict reducer fused into the
    # compiled step on real HBM. Same seed/shape as the cpu artifact so
    # the two reports diff leaf-for-leaf; the eval exits 5 on any gate
    # failure, so a red step here is a real regression, not noise.
    # Budget covers compile + 400 ticks + the eval fold.
    ("r15_predict", [sys.executable, "scripts/predict_eval.py",
                     "--seed", "0", "--ticks", "400",
                     "--backend", "tpu",
                     "--out", "reports/predict_hw_r15.json"],
     1800.0),
    # ---------------- round 16 (ISSUE 18: sparse synapse pools) -------
    # First silicon numbers for the member-index SP layout: the profiler
    # at the bench's measured-optimal rung (G=1024, T=32), sweeping the
    # kernel strategies on the NEW default (sparse member-index overlap +
    # S=2 TM lanes, 302,101 B/stream u16 vs 564,245 dense). The CPU
    # path is proven bit-exact against the oracle twins
    # (tests/parity/test_sparse_sp.py); this step answers the only open
    # question — whether the O(C*P) VPU pass beats the O(C*n_in) MXU
    # matmul on real HBM at the roofline (docs/KERNELS.md), and how far
    # the smaller state pushes the G-sweep OOM frontier.
    ("r16_sparse", [sys.executable, "scripts/profile_step.py",
                    "--T", "32", "--gs", "1024",
                    "--perm-bits", "16",
                    "--report", "chiprun_out/hw_session/profile_sparse_r16.json"],
     1800.0),
]


def step_budget(step: tuple, default: float) -> float:
    """STEPS entries are (name, cmd) or (name, cmd, budget)."""
    return step[2] if len(step) > 2 else default


def pick_steps(spec: str | None) -> list[tuple]:
    """Resolve a --steps '1,5,7' spec (1-based) against STEPS, loudly."""
    if not spec:
        return STEPS
    picked = []
    for tok in spec.split(","):
        i = int(tok)
        if not 1 <= i <= len(STEPS):
            raise SystemExit(
                f"--steps: {i} out of range (steps are 1..{len(STEPS)})"
            )
        picked.append(STEPS[i - 1])
    return picked


def obs_snapshot_path(name: str) -> str:
    """Per-step telemetry snapshot sink (rtap_tpu.obs JSONL). Children
    inherit it via $RTAP_OBS_SNAPSHOT: serve writes its final registry
    snapshot there (directly, or through live_soak's pass-through), so the
    session ledger reads structured tick/deadline facts instead of
    scraping stdout lines out of the step log."""
    return os.path.join(OUT, f"{name}.obs.jsonl")


def run_step(name: str, cmd: list[str], budget: float) -> int:
    """One step attempt; stdout+stderr -> <OUT>/<name>.log (overwrite).

    The step runs in its own session and a timeout kills the whole process
    GROUP: steps spawn grandchildren (`python -m rtap_tpu serve`, bench's
    attempt subprocesses) that must not outlive the timeout holding the TPU
    (and, historically, a fixed TCP port)."""
    import signal

    path = os.path.join(OUT, f"{name}.log")
    snap = obs_snapshot_path(name)
    try:
        os.remove(snap)  # fresh run, fresh telemetry (matches the log overwrite)
    except OSError:
        pass
    with open(path, "w") as f:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=f, stderr=subprocess.STDOUT,
                                start_new_session=True,
                                env={**os.environ, "RTAP_OBS_SNAPSHOT": snap})
        try:
            return proc.wait(timeout=budget)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                proc.kill()
            proc.wait()
            return -1


def log_tail(name: str, limit: int = 140) -> str:
    """Last nonempty line of a step's log, for one-line verdicts."""
    try:
        lines = [l.strip() for l in
                 open(os.path.join(OUT, f"{name}.log")).read().splitlines()
                 if l.strip()]
        return lines[-1][:limit] if lines else ""
    except OSError:
        return ""


def obs_tail(name: str) -> str:
    """Compact telemetry verdict from the step's obs snapshot (empty when
    the step emitted none — profiles and evals don't run the serve loop)."""
    from rtap_tpu.obs import read_last_snapshot, summarize_snapshot

    snap = read_last_snapshot(obs_snapshot_path(name))
    if snap is None:
        return ""
    s = summarize_snapshot(snap)
    parts = []
    for key, label in (("rtap_obs_ticks_total", "ticks"),
                       ("rtap_obs_missed_ticks_total", "missed"),
                       ("rtap_obs_scored_total", "scored"),
                       ("rtap_obs_alerts_total", "alerts"),
                       ("rtap_obs_routing_rebuilds_total", "rebuilds")):
        v = s.get(key)
        if v:
            parts.append(f"{label}={int(v)}")
    tick = s.get("rtap_obs_tick_seconds") or {}
    if tick.get("count"):
        parts.append(f"tick_mean={tick['mean'] * 1e3:.1f}ms"
                     f" tick_max={tick['max'] * 1e3:.0f}ms")
    return " ".join(parts)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget-per-step", type=float, default=600.0)
    ap.add_argument("--steps", default=None,
                    help="comma-separated 1-based step numbers (default all)")
    args = ap.parse_args()
    picked = pick_steps(args.steps)

    os.makedirs(OUT, exist_ok=True)
    for step in picked:
        name, cmd = step[0], step[1]
        budget = max(step_budget(step, args.budget_per_step), args.budget_per_step)
        log(f"step {name}: {' '.join(cmd[1:])} (budget {budget:.0f}s)")
        t0 = time.monotonic()
        rc = run_step(name, cmd, budget)
        dt = time.monotonic() - t0
        log(f"step {name}: rc={rc} in {dt:.0f}s — {log_tail(name)}")
        obs = obs_tail(name)
        if obs:
            log(f"step {name}: obs {obs}")


if __name__ == "__main__":
    main()
