"""chip_smoke.py — the quickest proof that rtap_tpu still starts on the chip.

Send it through the chip tool, one process per chip:

    python chip_smoke.py             # one chip: the score and serve phases
    python chip_smoke.py --chips 4   # four chips: the meshed path only

It drives the system's main path once through the entry points a user calls,
at the full width of the default model (cluster_preset: 256 columns x 8
cells, sparse pools, u16 permanences; depth = tick count is what is cut),
checks what comes out against the repo's own reference, and prints as its
LAST stdout line one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any phase failing, or a platform that is not ``tpu``, gives ``"ok": false``
and a non-zero exit — a CPU run is never reported as ok. Everything else
(max score difference, tick p50/p99, compile seconds, peak HBM) is printed
on earlier lines as observations: nothing here is a benchmark.

One process per chip: this parent never imports JAX. Each phase runs in a
child that holds the chip alone and has exited before the next starts; the
seeded feeder for ``serve`` is a thread of this JAX-free parent.

  score   StreamGroup(cluster_preset(), 1024 streams, backend="tpu") takes
          two 64-tick chunks of a seeded sine feed through dispatch_chunk /
          collect_chunk, learning on; raw scores of 8 streams are compared
          with the numpy oracle (HTMModel(backend="cpu")) over the same 128
          ticks at the hardware tolerance (1e-6: TPU f32 divide rounds 1 ulp
          differently from numpy).
  serve   ``python -m rtap_tpu serve`` as docs/DEPLOYMENT.md §1's "<= 4k,
          max quality" row runs it: default preset, --group-size 1024, 4,096
          streams from an @ids file (4 groups, 1.24 GB of model state), 1 s
          cadence, 40 ticks, --alerts, --obs-snapshot, fed over the real TCP
          listener. serve is crash-isolated by design — a group whose
          compile or dispatch fails is quarantined and the run STILL exits
          0 — so the verdict reads the event stream and the stats line, not
          the exit code.
  mesh    (--chips 4 only) StreamGroup(mesh=make_stream_mesh(4)) at 4,096
          streams against the same feed through an unmeshed group on one
          chip: shards on four distinct devices, no collective in the
          compiled step, scores bit-equal.

Artifacts (stats line, events, obs snapshot) land in chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 21
RAW_TOLERANCE = 1e-6  # verify skill: raw scores match the oracle to ~1e-7 on a TPU

#: the default run's sizes. Tests rehearse the control flow on the CPU at
#: tiny sizes with "rehearsal": True — the verdict still fails there (the
#: platform is not "tpu"); without it a non-TPU platform fails at once.
SIZES = {
    "score_streams": 1024, "score_chunk": 64, "oracle_streams": 8,
    "serve_streams": 4096, "serve_group": 1024, "serve_ticks": 40,
    "cadence_s": 1.0,
    "mesh_streams": 4096, "mesh_chunk": 16,
}
#: events on serve's alert stream that fail the smoke although serve exits 0
FAILING_EVENTS = ("group_quarantined", "group_restore_failed", "degraded",
                  "checkpoint_save_failed", "checkpoint_quarantined")


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ children ----
# (run in their own process: `python -c "import chip_smoke; ..."`)


def _device_or_fail(sizes: dict) -> dict:
    from rtap_tpu.utils.platform import enable_compile_cache, require_device

    device = require_device()
    enable_compile_cache()
    say(f"[device] {json.dumps(device)}")
    if device["platform"] != "tpu" and not sizes.get("rehearsal"):
        raise SystemExit(_result({"ok": False, "device": device,
                                  "why": "platform is not tpu"}))
    return device


def _result(res: dict) -> int:
    say("RESULT " + json.dumps(res))
    return 0 if res.get("ok") else 1


def _feed(G: int, T: int, chunk: int, phase=None):
    from rtap_tpu.utils.measure import make_sine_feed

    return make_sine_feed(G, T, key=(SEED, chunk), t0=chunk * T, phase=phase)


def _run_chunks(grp, G: int, T: int, n_chunks: int = 2):
    """n_chunks seeded chunks through dispatch/collect -> (values [n*T, G],
    ts, raw [n*T, G], per-chunk wall seconds)."""
    import numpy as np

    vals, tss, raws, walls, phase = [], [], [], [], None
    for c in range(n_chunks):
        v, ts, phase = _feed(G, T, c, phase)
        t0 = time.perf_counter()
        raw, _loglik, _alerts = grp.collect_chunk(
            grp.dispatch_chunk(v, ts, learn=True))
        walls.append(time.perf_counter() - t0)
        vals.append(v), tss.append(ts), raws.append(raw)
    return (np.concatenate(vals), np.concatenate(tss), np.concatenate(raws),
            walls)


def score_phase(sizes: dict) -> int:
    device = _device_or_fail(sizes)
    import jax
    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.models.htm_model import HTMModel
    from rtap_tpu.service.registry import StreamGroup

    checks: dict[str, bool] = {}
    G, T = sizes["score_streams"], sizes["score_chunk"]
    cfg = cluster_preset()
    grp = StreamGroup(cfg, [f"s{i:05d}" for i in range(G)], backend="tpu")
    vals, ts, raw, walls = _run_chunks(grp, G, T)
    say(f"[score] cluster_preset G={G}: 2 x {T} ticks, first chunk (cold "
        f"compile + run) {walls[0]:.1f}s, second {walls[1]:.2f}s")
    checks["scores_finite"] = bool(np.isfinite(raw).all())
    checks["tm_overflow_zero"] = int(np.asarray(grp.state["tm_overflow"]).sum()) == 0
    checks["learning_ran"] = int(np.asarray(grp.state["seg_last"]).max()) >= 0
    # the numpy oracle over the same ticks, streams spread across the group
    picks = np.linspace(0, G - 1, sizes["oracle_streams"]).astype(int)
    max_diff = 0.0
    for g in picks:
        oracle = HTMModel(cfg, seed=0, backend="cpu")
        ref = np.array([oracle.run(int(ts[i, g]), float(vals[i, g])).raw_score
                        for i in range(len(vals))], np.float32)
        max_diff = max(max_diff, float(np.abs(ref - raw[:, g]).max()))
    say(f"[score] oracle parity over {len(vals)} ticks x streams "
        f"{picks.tolist()}: max |raw - oracle| = {max_diff:.3g} "
        f"(tolerance {RAW_TOLERANCE:g})")
    checks["oracle_parity"] = max_diff <= RAW_TOLERANCE
    stats = jax.local_devices()[0].memory_stats() or {}
    say(f"[score] peak HBM {stats.get('peak_bytes_in_use')} B, in use "
        f"{stats.get('bytes_in_use')} B")
    del grp
    say(f"[score] checks {json.dumps(checks)}")
    return _result({"ok": all(checks.values()), "device": device,
                    "checks": checks})


def mesh_phase(sizes: dict) -> int:
    device = _device_or_fail(sizes)
    import jax
    import numpy as np

    from rtap_tpu.config import cluster_preset
    from rtap_tpu.ops.step import _sharded_chunk_fn
    from rtap_tpu.parallel import make_stream_mesh, put_sharded
    from rtap_tpu.service.registry import StreamGroup

    checks: dict[str, bool] = {"four_devices": device["count"] == 4}
    G, T = sizes["mesh_streams"], sizes["mesh_chunk"]
    cfg = cluster_preset()
    ids = [f"m{i:05d}" for i in range(G)]
    mesh = make_stream_mesh(4)
    meshed = StreamGroup(cfg, ids, backend="tpu", mesh=mesh)

    def placement(state) -> bool:
        ok = True
        for k, leaf in state.items():
            shards = leaf.addressable_shards
            devs = {s.device for s in shards}
            rows = {s.data.shape[0] for s in shards}
            if len(devs) != 4 or rows != {G // 4}:
                say(f"[mesh] leaf {k}: {len(devs)} devices, rows {sorted(rows)}")
                ok = False
        return ok

    # `resident`: the leaves as the devices hold them (ops/resident.py)
    checks["shards_placed"] = placement(meshed.resident)
    _, _, raw_mesh, mw = _run_chunks(meshed, G, T)
    # donation must keep every leaf where it was put
    checks["shards_stay_placed"] = placement(meshed.resident)
    say(f"[mesh] cluster_preset G={G} over {sorted(str(d) for d in mesh.devices.flat)}: "
        f"{len(meshed.resident)} state leaves x 4 shards of {G // 4} rows; "
        f"first chunk {mw[0]:.1f}s, second {mw[1]:.2f}s")

    state_ranks = tuple(sorted((k, max(np.ndim(v), 1))
                               for k, v in meshed.resident.items()))
    v, ts, _ = _feed(G, T, 0)
    txt = _sharded_chunk_fn(cfg, mesh, True, state_ranks).lower(
        meshed.resident, put_sharded(v[..., None], mesh, 1),
        put_sharded(ts.astype(np.int32), mesh, 1)).compile().as_text()
    found = [c for c in ("all-reduce", "all-gather", "collective-permute",
                         "all-to-all", "reduce-scatter") if c in txt]
    say(f"[mesh] collectives in the compiled step: {found or 'none'}")
    checks["collective_free"] = not found
    per_device = [d.memory_stats() or {} for d in jax.local_devices()]
    say(f"[mesh] HBM in use per device "
        f"{[s.get('bytes_in_use') for s in per_device]}")
    del meshed

    plain = StreamGroup(cfg, ids, backend="tpu")
    _, _, raw_one, ow = _run_chunks(plain, G, T)
    diff = float(np.abs(raw_mesh - raw_one).max())
    say(f"[mesh] one-chip control G={G}: first chunk {ow[0]:.1f}s, second "
        f"{ow[1]:.2f}s; max |raw_mesh - raw_one| = {diff:.3g}, bit-equal "
        f"{bool(np.array_equal(raw_mesh, raw_one))}")
    checks["scores_finite"] = bool(np.isfinite(raw_mesh).all())
    checks["matches_one_chip"] = bool(np.array_equal(raw_mesh, raw_one))
    say(f"[mesh] checks {json.dumps(checks)}")
    return _result({"ok": all(checks.values()), "device": device,
                    "checks": checks})


def _child_main() -> int:
    phase, sizes = sys.argv[1], json.loads(sys.argv[2])
    return {"score": score_phase, "mesh": mesh_phase}[phase](sizes)


# -------------------------------------------------------------- parent ----
# (never imports JAX: a parent that touched it would hold the chip)


def _run_child(phase: str, sizes: dict, timeout_s: float) -> dict:
    """One phase in its own process -> its RESULT dict ({"ok": False} when
    it died without one). Its lines pass through to this stdout."""
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, chip_smoke; sys.exit(chip_smoke._child_main())",
         phase, json.dumps(sizes)],
        cwd=HERE, stdout=subprocess.PIPE, text=True)
    result: dict = {"ok": False, "why": f"{phase} child left no result"}
    killer = threading.Timer(timeout_s, proc.kill)
    killer.start()
    try:
        for line in proc.stdout:
            if line.startswith("RESULT "):
                result = json.loads(line[len("RESULT "):])
            else:
                say(line.rstrip("\n"))
        rc = proc.wait()
    finally:
        killer.cancel()
    if rc != 0 and result.get("ok"):
        result = {**result, "ok": False, "why": f"{phase} child exited {rc}"}
    return result


class _Feeder:
    """Seeded JSONL producer for serve's TCP listener: every stream, twice
    per cadence (so no serve tick can fall between two pushes), ``ts``
    anchored ahead of the wall clock — serve clamps source timestamps
    monotonic against it, and a feed in the past would freeze there."""

    def __init__(self, port: int, ids: list[str], cadence_s: float):
        self.port, self.ids, self.period = port, ids, cadence_s / 2.0
        self.stop = threading.Event()
        self.pushes = 0
        self.error: str | None = None
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="chip-smoke-feeder")

    def _run(self) -> None:
        try:
            from rtap_tpu.utils.measure import make_sine_feed

            prefixes = [f'{{"id": "{sid}", "value": ' for sid in self.ids]
            anchor, t0 = int(time.time()) + 5, time.monotonic()
            phase = None
            with socket.create_connection(("127.0.0.1", self.port),
                                          timeout=30.0) as sock:
                while not self.stop.is_set():
                    t_push = time.monotonic()
                    chunk, _, phase = make_sine_feed(
                        len(self.ids), 1, key=(SEED, 1000 + self.pushes),
                        t0=self.pushes, phase=phase)
                    suffix = f', "ts": {anchor + int(t_push - t0)}}}\n'
                    sock.sendall("".join(
                        p + repr(v) + suffix for p, v in
                        zip(prefixes, chunk[0].astype(float).tolist())).encode())
                    self.pushes += 1
                    self.stop.wait(max(0.0, self.period
                                       - (time.monotonic() - t_push)))
        except (BrokenPipeError, ConnectionResetError):
            pass  # serve finished its tick budget and closed the listener
        except Exception as e:  # noqa: BLE001 — recorded and failed on
            self.error = f"{type(e).__name__}: {e}"


def judge_serve(rc: int, stats: dict, events: list[dict], sizes: dict,
                feeder_error: str | None) -> dict[str, bool]:
    """The serve verdict, from what serve itself reported: its exit code is
    the least of it (a quarantined group still exits 0)."""
    # cluster_preset u16 bytes/stream, derived statically (pure AST, no jax;
    # tests/integration/test_bringup.py holds it equal to the real arrays'
    # byte sum)
    from rtap_tpu.analysis.scalingmath import derived_stream_bytes

    n, ticks = sizes["serve_streams"], sizes["serve_ticks"]
    state_bytes = n * derived_stream_bytes(HERE, 16)
    by_group = stats.get("scored_by_group") or []
    bad = [e for e in events if e.get("event") in FAILING_EVENTS]
    for e in bad[:8]:
        say(f"[serve] failing event: {json.dumps(e)[:300]}")
    return {
        "exit_zero": rc == 0,
        "ticks_as_asked": stats.get("ticks") == ticks,
        "scored_enough": stats.get("scored", 0) >= n * (ticks - 2),
        "groups_scored_equally": len(by_group) == -(-n // sizes["serve_group"])
        and len(set(by_group)) == 1,
        "no_failing_events": not bad and not stats.get("quarantined")
        and not stats.get("checkpoint_save_failures"),
        "no_cold_compile_after_warmup":
            stats.get("cold_compiles_after_warmup") == 0,
        "records_flowed": stats.get("records_parsed", 0) >= n * (ticks - 2)
        and stats.get("parse_errors") == 0 and feeder_error is None,
        "native_parser": stats.get("native_active") is True,
        # off the chip (rehearsal) the CPU backend reports no memory stats
        "state_on_device": stats.get("platform") != "tpu"
        or stats.get("hbm_bytes_in_use", 0) >= state_bytes,
        "no_hbm_error": "hbm_error" not in stats,
    }


def serve_phase(sizes: dict, out_dir: str, extra_args: tuple = ()) -> dict:
    n, ticks = sizes["serve_streams"], sizes["serve_ticks"]
    cadence = sizes["cadence_s"]
    ids = [f"node{i // 4:04d}.m{i % 4}" for i in range(n)]
    ids_path = os.path.join(out_dir, "ids.txt")
    alerts_path = os.path.join(out_dir, "alerts.jsonl")
    snap_path = os.path.join(out_dir, "obs_snapshot.jsonl")
    with open(ids_path, "w") as f:
        f.write("\n".join(ids) + "\n")
    cmd = [sys.executable, "-m", "rtap_tpu", "serve", "--backend", "tpu",
           "--streams", "@" + ids_path, "--group-size",
           str(sizes["serve_group"]), "--cadence", str(cadence),
           "--ticks", str(ticks), "--port", "0", "--alerts", alerts_path,
           "--obs-snapshot", snap_path, *extra_args]
    say(f"[serve] {' '.join(cmd[1:])}")
    t_start = time.monotonic()
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    stderr_lines: list[str] = []
    port: list[int] = []
    listening = threading.Event()

    def drain() -> None:
        for line in proc.stderr:
            stderr_lines.append(line)
            m = re.search(r"listening for JSONL records on \S+?:(\d+)", line)
            if m:
                port.append(int(m.group(1)))
                listening.set()
        listening.set()  # EOF: serve is gone, stop waiting

    threading.Thread(target=drain, daemon=True,
                     name="chip-smoke-stderr").start()
    killer = threading.Timer(450.0 + 3 * ticks * cadence, proc.kill)
    killer.start()
    feeder = None
    try:
        listening.wait()
        if port:
            feeder = _Feeder(port[0], ids, cadence)
            feeder.thread.start()
        out = proc.stdout.read()  # EOF = serve exited
        rc = proc.wait()
    finally:
        killer.cancel()
        if feeder is not None:
            feeder.stop.set()
            feeder.thread.join(timeout=10)
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    wall = time.monotonic() - t_start
    try:
        stats = json.loads(out.strip().splitlines()[-1])
    except (IndexError, ValueError):
        stats = {}
    if rc != 0 or not stats:
        say("[serve] stderr tail:\n" + "".join(stderr_lines[-30:]))
    events = []
    if os.path.exists(alerts_path):
        with open(alerts_path) as f:
            events = [json.loads(line) for line in f
                      if line.startswith('{"event"')]
    with open(os.path.join(out_dir, "serve_stats.json"), "w") as f:
        json.dump(stats, f, indent=1)
    device = {"platform": stats.get("platform"),
              "kind": stats.get("device_kind"),
              "count": stats.get("device_count")}
    say(f"[serve] device as serve's stats line reports it: {json.dumps(device)}")
    say("[serve] observed (not asserted, not a benchmark): "
        f"tick p50 {stats.get('latency_p50_ms')} ms, p99 "
        f"{stats.get('latency_p99_ms')} ms, max {stats.get('latency_max_ms')} "
        f"ms; missed deadlines {stats.get('missed_deadlines')}/"
        f"{stats.get('ticks')}; wall {wall:.1f}s of which the loop "
        f"{stats.get('elapsed_s')}s for {ticks} x {cadence}s ticks (the rest "
        "of the loop's time is the AOT warm-up compile); peak HBM "
        f"{stats.get('hbm_peak_bytes_in_use')} B, in use "
        f"{stats.get('hbm_bytes_in_use')} B; alerts {stats.get('alerts')}; "
        f"events {sorted({e.get('event') for e in events})}; feeder pushes "
        f"{feeder.pushes if feeder else 0}")
    checks = judge_serve(rc, stats, events, sizes,
                         feeder.error if feeder else "serve never listened")
    say(f"[serve] checks {json.dumps(checks)}")
    return {"ok": all(checks.values()), "device": device, "checks": checks}


def run(chips: int, sizes: dict, out_dir: str) -> int:
    """All phases for `chips` -> exit code; prints the contract's last line."""
    if os.path.isdir(out_dir):
        shutil.rmtree(out_dir)
    os.makedirs(out_dir)
    if chips == 4:
        results = [_run_child("mesh", sizes, 900.0)]
    else:
        results = [_run_child("score", sizes, 500.0)]
        device = results[0].get("device") or {}
        if device.get("platform") == "tpu" or sizes.get("rehearsal"):
            results.append(serve_phase(sizes, out_dir))
        # (no TPU and no rehearsal: the full-size serve on a CPU would only
        # burn the time limit on its way to the same "ok": false)
    device = results[-1].get("device") or results[0].get("device") or {}
    same_device = all(r.get("device") == device for r in results)
    ok = (all(r.get("ok") for r in results) and same_device
          and device.get("platform") == "tpu" and device.get("count") == chips)
    if not same_device:
        say(f"phases disagree on the device: {[r.get('device') for r in results]}")
    say(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): score + serve on one chip; 4: the "
                         "meshed StreamGroup path and its one-chip control, "
                         "no other phase")
    args = ap.parse_args(argv)
    try:
        import rtap_tpu  # noqa: F401 — numpy-only at import; no backend
    except ImportError as e:
        say(f"chip_smoke: the rtap_tpu package is not importable here: {e}")
        say(json.dumps({"ok": False, "device": {}}))
        return 1
    return run(args.chips, SIZES,
               os.path.join(HERE, "chiprun_out", "chip_smoke"))


if __name__ == "__main__":
    sys.exit(main())
