"""Live-serving soak at realistic scale (round-3 verdict, weak #7).

Round 3's live-path evidence was smoke-scale (a handful of streams, ~12
ticks at 0.1 s cadence). The round-2 ask was "zero missed deadlines at a
realistic G": this script runs the REAL operator surface —
``python -m rtap_tpu serve`` with G >= 1024 streams at 1 s cadence for >= 5
minutes, fed by an external TCP JSONL producer (the reference's
collector-push shape, SURVEY.md §3.3) — and commits the resulting stats
(missed deadlines, p50/p90/p99 tick latency, throughput, HBM occupancy) to
reports/live_soak.json.

The serve child binds an EPHEMERAL port (parsed from its own "listening"
line) so a previous attempt's orphan can never answer the readiness probe;
the feeder runs in THIS process as a real network producer, its pushed-tick
count and any death are recorded in the artifact, and a feeder that died
mid-soak fails the run (a "zero missed deadlines" line is only evidence if
data was actually flowing). Values follow the diurnal sine + noise profile
so the TM keeps learning novel input for the whole soak.

Usage: python scripts/live_soak.py [--streams 1024] [--ticks 330]
       [--cadence 1.0] [--backend tpu]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the feeder imports rtap_tpu in THIS process; running as `python
# scripts/live_soak.py` puts scripts/ (not the repo) at sys.path[0]
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import force_cpu_requested  # noqa: E402

FEEDER_DIED_EXIT = 5


def log(msg: str) -> None:
    print(f"[soak] {msg}", file=sys.stderr, flush=True)


class Feeder:
    """Push one record per stream per cadence over one persistent connection.

    Tracks `ticks_pushed` and records any fatal `error` instead of dying
    silently — the soak artifact must say whether data was actually flowing.
    """

    def __init__(self, port: int, ids: list[str], cadence_s: float,
                 churn_every: int = 0, binary: bool = False):
        self.port = port
        self.ids = list(ids)
        self.cadence_s = cadence_s
        # elastic churn (validates serve --auto-register/--auto-release-
        # after under deadline): every N pushed ticks, stop feeding one
        # original stream (it will be auto-released) and start feeding a
        # brand-new id (it will be auto-registered into freed capacity)
        self.churn_every = int(churn_every)
        self.churned = 0
        self.stop = threading.Event()
        self.ticks_pushed = 0
        self.error: str | None = None
        # binary: push RB1 batch frames over the persistent connection
        # (serve --ingest-port) instead of JSONL lines — one vectorized
        # frame per tick, no per-record formatting at all (the JSONL
        # feeder's ~350 ms/tick json cost at the 100k shape disappears)
        self.binary = bool(binary)
        self.thread = threading.Thread(
            target=self._run_binary if binary else self._run, daemon=True)

    def _run_binary(self) -> None:
        phase = None
        try:
            import numpy as np

            from rtap_tpu.ingest.emit import BinaryFeedConnection
            from rtap_tpu.ingest.protocol import data_frame
            from rtap_tpu.utils.measure import make_sine_feed

            conn = BinaryFeedConnection(("127.0.0.1", self.port),
                                        timeout_s=30.0)
            codes = None
            pending_names: set[str] = set()
            while not self.stop.is_set():
                t_start = time.perf_counter()
                ts = int(time.time())
                chunk, _, phase = make_sine_feed(
                    len(self.ids), 1, key=(7, 42 + self.ticks_pushed),
                    t0=self.ticks_pushed, phase=phase,
                )
                if conn.poll_map():
                    # serve pushed a fresh map (ANY membership change —
                    # e.g. an auto-release — bumps the epoch, and stale-
                    # epoch frames are refused whole): re-encode
                    codes = None
                if codes is None or len(codes) != len(self.ids):
                    codes = np.array(
                        [conn.code_of.get(s, -1) for s in self.ids],
                        np.int64)
                known = codes >= 0
                if known.any():
                    conn.send_frame(data_frame(
                        codes[known].astype(np.uint32),
                        chunk[0].astype(np.float32)[known], ts,
                        epoch=conn.epoch))
                self.ticks_pushed += 1
                if self.churn_every and \
                        self.ticks_pushed % self.churn_every == 0:
                    ci = self.churned % len(self.ids)
                    self.ids[ci] = f"churn{self.churned:04d}.m0"
                    self.churned += 1
                    pending_names.add(self.ids[ci])
                    conn.send_names(sorted(pending_names))
                    codes = None
                if pending_names:
                    # serve's membership block claims announced names at
                    # tick boundaries; refresh the map until they appear
                    # EVERY tick — each claim also bumps the map epoch,
                    # and frames stamped with the old epoch are refused
                    # (stale-code protection), so a lazy refresh here
                    # would go deaf for real streams too
                    conn.refresh_map()
                    pending_names -= set(conn.code_of)
                    codes = None
                budget = self.cadence_s - (time.perf_counter() - t_start)
                if budget > 0:
                    self.stop.wait(budget)
            conn.close()
        except (BrokenPipeError, ConnectionResetError):
            pass  # serve finished its tick budget and closed the listener
        except Exception as e:  # noqa: BLE001 — recorded, surfaced, fatal
            self.error = f"{type(e).__name__}: {e}"

    def _run(self) -> None:
        phase = None  # first chunk draws it; passed back for continuity
        prefixes = None  # per-id JSON prefixes, rebuilt when membership changes
        try:
            # inside the try: an import failure (the exact class of bug the
            # sys.path fix above addresses) must land in self.error, not
            # kill the thread silently and read as a connection drop
            import numpy as np

            from rtap_tpu.utils.measure import make_sine_feed

            sock = socket.create_connection(("127.0.0.1", self.port), timeout=5.0)
            # a paced producer should tolerate serve stalling a few ticks
            # (device hiccup) without dying; 30 s of backpressure = fatal
            sock.settimeout(30.0)
            f = sock.makefile("wb")
            while not self.stop.is_set():
                t_start = time.perf_counter()
                ts = int(time.time())
                # the same diurnal profile every other experiment feeds;
                # per-tick key = fresh noise (make_sine_feed reseeds per
                # call — the multigroup/measure chunk idiom), phase threads
                # stream continuity
                chunk, _, phase = make_sine_feed(
                    len(self.ids), 1, key=(7, 42 + self.ticks_pushed),
                    t0=self.ticks_pushed, phase=phase,
                )
                # hand-formatted JSON (parse-identical to json.dumps for
                # these plain floats/strings, spot-checked at init below):
                # at the 100k-stream soak shape json.dumps alone costs
                # ~350 ms of the 1 s cadence on the 1-core host; prefix
                # precompute + f-string is ~3.3x cheaper
                if prefixes is None or len(prefixes) != len(self.ids):
                    prefixes = [f'{{"id": "{sid}", "value": ' for sid in self.ids]
                suffix = f', "ts": {ts}}}\n'
                if np.isfinite(chunk).all():
                    lines = [p + repr(v) + suffix for p, v in
                             zip(prefixes, chunk[0].astype(float).tolist())]
                else:
                    # ADVICE r5: repr() on a non-finite float emits bare
                    # 'nan'/'inf', which json.loads rejects — the fast path
                    # is only parse-identical for finite values. json.dumps
                    # serializes the odd non-finite row as NaN/Infinity
                    # (accepted by the Python consumer path) instead of
                    # silently corrupting the record stream.
                    lines = [json.dumps({"id": sid, "value": v, "ts": ts}) + "\n"
                             for sid, v in
                             zip(self.ids, chunk[0].astype(float).tolist())]
                if self.ticks_pushed == 0:
                    rec = json.loads(lines[0])
                    assert rec == {"id": self.ids[0],
                                   "value": float(chunk[0][0]), "ts": ts}, rec
                f.write("".join(lines).encode())
                f.flush()
                self.ticks_pushed += 1
                if self.churn_every and \
                        self.ticks_pushed % self.churn_every == 0:
                    # rotate: drop the oldest still-original id, add a new
                    # one (values keep coming from the same feed column, so
                    # the signal stays realistic for the claimed model)
                    ci = self.churned % len(self.ids)
                    self.ids[ci] = f"churn{self.churned:04d}.m0"
                    prefixes[ci] = f'{{"id": "{self.ids[ci]}", "value": '
                    self.churned += 1
                budget = self.cadence_s - (time.perf_counter() - t_start)
                if budget > 0:
                    self.stop.wait(budget)
            f.close()
            sock.close()
        except (BrokenPipeError, ConnectionResetError):
            pass  # serve finished its tick budget and closed the listener
        except Exception as e:  # noqa: BLE001 — recorded, surfaced, fatal
            self.error = f"{type(e).__name__}: {e}"


def wait_for_listener(proc: subprocess.Popen, stderr_lines: list[str],
                      deadline_s: float) -> int:
    """Parse serve's own 'listening for JSONL records on host:port' stderr
    line -> bound port. Only THIS child's line is trusted (an orphan from a
    killed earlier attempt can answer a connect-probe; it cannot write to
    this process's pipe)."""
    pat = re.compile(r"listening for (?:JSONL records|binary batch frames) "
                     r"on \S+?:(\d+)")
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        for line in stderr_lines:
            m = pat.search(line)
            if m:
                return int(m.group(1))
        if proc.poll() is not None:
            sys.stderr.write("".join(stderr_lines))
            log(f"serve exited early rc={proc.returncode}")
            # propagate the child's code (e.g. the device rule's refusal
            # to start without a TPU)
            raise SystemExit(proc.returncode)
        time.sleep(0.25)
    raise SystemExit("serve never reported its TCP listener")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--streams", type=int, default=1024)
    ap.add_argument("--ticks", type=int, default=330)
    ap.add_argument("--cadence", type=float, default=1.0)
    ap.add_argument("--backend", default="tpu")
    ap.add_argument("--group-size", type=int, default=1024,
                    help="passed through to serve: streams per device group "
                         "(multi-group interleaved serving when exceeded)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="passed through to serve: 2 hides the per-group "
                         "device round trip behind the cadence sleep")
    ap.add_argument("--dispatch-threads", type=int, default=1,
                    help="passed through to serve: overlap the per-group "
                         "blocking dispatch calls (the ~65 ms/group serial "
                         "floor of a chip that is not host-local, which "
                         "depth 2 alone cannot touch)")
    ap.add_argument("--columns", type=int, default=None,
                    help="passed through to serve: width-scaled cluster "
                         "preset (the density lever; SCALING.md)")
    ap.add_argument("--learn-every", type=int, default=1,
                    help="passed through to serve: learning cadence")
    ap.add_argument("--learn-full-until", type=int, default=None,
                    help="passed through to serve: 0 = mature-steady-state "
                         "capability semantics (the r5 soak forensics: the "
                         "default 300-tick full-rate window covered 91%% of "
                         "a 330-tick soak, masking the cadence entirely)")
    ap.add_argument("--micro-chunk", type=int, default=1,
                    help="passed through to serve: M ticks per device "
                         "dispatch (the per-program-floor amortizer)")
    ap.add_argument("--chunk-stagger", action="store_true",
                    help="passed through to serve: rotate micro-chunk "
                         "boundaries across groups (boundary-spike leveler)")
    ap.add_argument("--stagger-learn", action="store_true",
                    help="passed through to serve: stagger cadence phase "
                         "across groups (the 100k-serving load-spreading "
                         "shape)")
    ap.add_argument("--freeze", action="store_true",
                    help="passed through to serve: inference-only soak")
    ap.add_argument("--binary-ingest", action="store_true",
                    help="feed serve through the RB1 binary batch protocol "
                         "(serve --ingest-port) instead of per-record "
                         "JSONL: one vectorized frame per tick from the "
                         "feeder, zero per-record Python on either side "
                         "(ISSUE 7 wire-speed ingest; docs/INGEST.md)")
    ap.add_argument("--churn-every", type=int, default=0,
                    help="elastic-churn soak: every N feeder ticks, rotate "
                         "one stream id (old goes silent -> auto-released; "
                         "new appears -> auto-registered). Enables serve "
                         "--auto-register and --auto-release-after "
                         "(2x churn interval) automatically")
    ap.add_argument("--health", action="store_true",
                    help="arm the serve child's model-health reducers "
                         "(serve --health): fused on-device occupancy/"
                         "sparsity/score aggregates + scorecards; the "
                         "fleet gauges land in the obs snapshot this "
                         "soak reads back")
    ap.add_argument("--predict", action="store_true",
                    help="arm the serve child's predictive horizon "
                         "(serve --predict): fused predict reducer + "
                         "precursor paging; the predict fleet gauges "
                         "land in the obs snapshot this soak reads back "
                         "(docs/PREDICT.md)")
    ap.add_argument("--predict-horizon", type=int, default=None,
                    help="passed through to serve: score the forward "
                         "model k ticks ahead (implies --predict)")
    ap.add_argument("--threshold", type=float, default=None,
                    help="passed through to serve: alert threshold "
                         "(lower it to densify alert traffic when the "
                         "detect-latency sketch needs samples)")
    ap.add_argument("--latency", action="store_true",
                    help="arm the serve child's detection-latency "
                         "tracking (serve --latency): stage waterfalls, "
                         "windowed quantile sketches, lag gauges — the "
                         "latency/slo blocks land in this soak's report")
    ap.add_argument("--latency-window", type=int, default=None,
                    help="passed through to serve: sketch window ticks")
    ap.add_argument("--slo", action="append", default=None,
                    metavar="NAME=TARGET@pQ",
                    help="passed through to serve (repeatable): declare "
                         "a latency SLO, e.g. detect=2s@p99; the run's "
                         "SLO verdict is recorded in the report "
                         "(slo_verdict) and a burn dumps a postmortem "
                         "when --postmortem-dir is armed. Implies "
                         "--latency")
    ap.add_argument("--slo-fast-window", type=int, default=None,
                    help="passed through to serve: fast burn window ticks")
    ap.add_argument("--slo-slow-window", type=int, default=None,
                    help="passed through to serve: slow burn window ticks")
    ap.add_argument("--jax-trace", default=None,
                    help="passed through to serve: wrap the soak window in "
                         "jax.profiler.trace writing the XLA device trace "
                         "to this directory (the hw_session device-trace "
                         "step pairs it with the host span timeline)")
    ap.add_argument("--trace-out", default=None,
                    help="passed through to serve: write the host span "
                         "timeline as Perfetto-loadable Chrome trace JSON")
    ap.add_argument("--postmortem-dir", default=None,
                    help="passed through to serve: arm the flight "
                         "recorder (auto postmortem bundles on "
                         "quarantine/degradation/miss-burst/crash)")
    ap.add_argument("--startup-timeout", type=float, default=420.0,
                    help="budget for serve's backend init + first compile")
    ap.add_argument("--out", default=os.path.join(REPO, "reports", "live_soak.json"))
    ap.add_argument("--obs-snapshot", default=None,
                    help="telemetry snapshot JSONL the serve child writes "
                         "and this script reads back into the artifact "
                         "(default: $RTAP_OBS_SNAPSHOT, else <out>.obs.jsonl)")
    args = ap.parse_args()
    obs_snapshot = args.obs_snapshot \
        or os.environ.get("RTAP_OBS_SNAPSHOT") \
        or args.out + ".obs.jsonl"
    # fresh run, fresh telemetry: a stale snapshot line from an earlier
    # attempt must never be read back as this run's evidence
    try:
        os.remove(obs_snapshot)
    except OSError:
        pass

    ids = [f"node{i // 4:04d}.m{i % 4}" for i in range(args.streams)]
    alerts_path = os.path.join(REPO, "reports", "live_soak_alerts.jsonl")
    # @file form always: a 16k-stream comma list exceeds MAX_ARG_STRLEN
    # (observed: live_soak_16k step died "Argument list too long").
    # Per-run temp file: a fixed path would let concurrent soaks swap id
    # sets under each other mid-startup, and would leave junk in reports/
    import tempfile

    fd, ids_path = tempfile.mkstemp(prefix="live_soak_ids_", suffix=".txt")
    with os.fdopen(fd, "w") as f:
        f.write("\n".join(ids) + "\n")
    cmd = [
        sys.executable, "-m", "rtap_tpu", "serve",
        "--streams", "@" + ids_path,
        *(["--ingest-port", "0"] if args.binary_ingest
          else ["--port", "0"]),
        "--ticks", str(args.ticks),
        "--cadence", str(args.cadence),
        "--backend", args.backend,
        "--group-size", str(args.group_size),
        "--pipeline-depth", str(args.pipeline_depth),
        "--dispatch-threads", str(args.dispatch_threads),
        "--alerts", alerts_path,
        "--obs-snapshot", obs_snapshot,
    ]
    if args.columns is not None:
        cmd += ["--columns", str(args.columns)]
    if args.learn_every != 1:
        cmd += ["--learn-every", str(args.learn_every)]
    if args.stagger_learn:
        cmd += ["--stagger-learn"]
    if args.micro_chunk != 1:
        cmd += ["--micro-chunk", str(args.micro_chunk)]
    if args.learn_full_until is not None:
        cmd += ["--learn-full-until", str(args.learn_full_until)]
    if args.chunk_stagger:
        cmd += ["--chunk-stagger"]
    if args.freeze:
        cmd += ["--freeze"]
    if args.health:
        cmd += ["--health"]
    if args.predict or args.predict_horizon is not None:
        cmd += ["--predict"]
    if args.predict_horizon is not None:
        cmd += ["--predict-horizon", str(args.predict_horizon)]
    if args.threshold is not None:
        cmd += ["--threshold", str(args.threshold)]
    if args.latency or args.slo:
        cmd += ["--latency"]
    if args.latency_window is not None:
        cmd += ["--latency-window", str(args.latency_window)]
    for spec in args.slo or ():
        cmd += ["--slo", spec]
    if args.slo_fast_window is not None:
        cmd += ["--slo-fast-window", str(args.slo_fast_window)]
    if args.slo_slow_window is not None:
        cmd += ["--slo-slow-window", str(args.slo_slow_window)]
    if args.jax_trace:
        cmd += ["--jax-trace", args.jax_trace]
    if args.trace_out:
        cmd += ["--trace-out", args.trace_out]
    if args.postmortem_dir:
        cmd += ["--postmortem-dir", args.postmortem_dir]
    if args.churn_every:
        cmd += ["--auto-register",
                "--auto-release-after", str(2 * args.churn_every)]
    log(f"starting serve: G={args.streams} ticks={args.ticks} "
        f"cadence={args.cadence}s backend={args.backend}")
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    stderr_lines: list[str] = []
    drain = threading.Thread(
        target=lambda: stderr_lines.extend(iter(proc.stderr.readline, "")),
        daemon=True)
    drain.start()

    feeder = None
    try:
        port = wait_for_listener(proc, stderr_lines, args.startup_timeout)
        feeder = Feeder(port, ids, args.cadence,
                        churn_every=args.churn_every,
                        binary=args.binary_ingest)
        feeder.thread.start()
        log(f"feeder attached on port {port}; soaking...")
        out = proc.stdout.read()  # EOF = serve exited; drain thread owns stderr
        proc.wait()
    finally:
        if feeder is not None:
            feeder.stop.set()
            feeder.thread.join(timeout=5)
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        try:
            os.remove(ids_path)
        except OSError:
            pass
    if proc.returncode != 0:
        sys.stderr.write("".join(stderr_lines))
        log(f"serve failed rc={proc.returncode}")
        raise SystemExit(proc.returncode)

    stats = json.loads(out.strip().splitlines()[-1])
    # the serve child's telemetry registry, read from its snapshot file
    # rather than scraped out of stdout/stderr: the obs seam (rtap_tpu.obs)
    # is the structured surface for tick/phase/deadline accounting
    from rtap_tpu.obs import read_last_snapshot, summarize_snapshot

    snap = read_last_snapshot(obs_snapshot)
    obs_summary = summarize_snapshot(snap) if snap else None
    if obs_summary is None:
        log(f"warning: serve left no telemetry snapshot at {obs_snapshot}")
    n_alert_lines = 0
    n_event_lines = 0
    if os.path.exists(alerts_path):
        with open(alerts_path) as f:
            for line in f:
                # watchdog events share the alert stream; json.dumps puts
                # their discriminating "event" key first, so this split is
                # exact without parsing a potentially huge file
                if line.startswith('{"event"'):
                    n_event_lines += 1
                else:
                    n_alert_lines += 1
        os.remove(alerts_path)  # large; the count is the committed evidence
    result = {
        "streams": args.streams, "ticks": args.ticks, "cadence_s": args.cadence,
        "backend": args.backend, "group_size": args.group_size,
        # an honest artifact must say WHERE the group path actually ran:
        # backend="tpu" under RTAP_FORCE_CPU=1 is the JAX group kernels on
        # the CPU platform, not the chip — serve's own stats line names the
        # device it scored on (null for the cpu oracle backend)
        "forced_cpu": force_cpu_requested(),
        "platform": stats.get("platform"),
        "device_kind": stats.get("device_kind"),
        "device_count": stats.get("device_count"),
        # model config the numbers were measured under — a width-scaled or
        # cadence-thinned soak must be distinguishable from a default one
        "columns": args.columns, "learn_every": args.learn_every,
        "stagger_learn": args.stagger_learn,
        "micro_chunk": args.micro_chunk,
        "learn_full_until": args.learn_full_until,
        "chunk_stagger": args.chunk_stagger,
        "binary_ingest": args.binary_ingest,
        "churn_every": args.churn_every, "ids_churned": feeder.churned,
        "alert_lines": n_alert_lines,
        "event_lines": n_event_lines,
        "feeder_ticks_pushed": feeder.ticks_pushed,
        "feeder_error": feeder.error, **stats,
        # the SLO verdict under a stable key (ISSUE 11): **stats already
        # carries "slo"/"latency" when armed, but harnesses key on this
        "slo_verdict": stats.get("slo"),
        "obs": obs_summary,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    if feeder.error is not None:
        log(f"feeder died mid-soak: {feeder.error} — failing the run")
        return FEEDER_DIED_EXIT
    if feeder.ticks_pushed < args.ticks - 2:
        # BrokenPipe is normal at the END (serve closes after its tick
        # budget); a connection drop mid-soak leaves error=None but a tick
        # shortfall — a "zero missed deadlines" line without data flowing
        # is not evidence (2 ticks of slack: the final tick can race
        # serve's close)
        log(f"feeder pushed only {feeder.ticks_pushed}/{args.ticks} ticks "
            f"— connection dropped mid-soak; failing the run")
        return FEEDER_DIED_EXIT
    return 0


if __name__ == "__main__":
    sys.exit(main())
