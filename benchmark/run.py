"""One cell, one run, one fresh process:

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (state on the device from the seed, warm-up of the cell's own shapes,
generator start and phase-lock) is timed as `setup_s`; then the cell's
traffic kind measures for --seconds; then — outside both — the timed path's
output is compared with the plain reference. The LAST stdout line is the
result object (correct, attempted, failed, metrics, device[, breakdown],
with reference_s, compared_ticks and, last, `compared`: each number beside
its limit, which are also the last lines of stderr); earlier lines say what
ran. With --trace 0 the metrics are the cell's
end-to-end metrics, with --trace 1 (profiler on) its per-layer metrics.
Without a TPU the run exits non-zero and prints no result."""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from benchmark import check, program, scoped_trace, trace_reduce  # noqa: E402
from benchmark.registry import REPO, Registry  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


class RunContext:
    """What a traffic kind gets: the cell's files, the arguments, and the
    harness's clocks (set-up spans, the set-up/window boundary, the compile
    counter, the profiler)."""

    def __init__(self, root, cell, seed, seconds, trace, control, allow_cpu,
                 hooks, t0):
        self.root, self.cell = root, cell
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control, self.allow_cpu, self.hooks = control, allow_cpu, hooks
        self.say = say
        self.t0 = t0
        self.t_setup_done = None
        self.bench_spans: dict[str, tuple[float, float]] = {}
        self.compiles = program.CompileCounter()
        self.trace_dir = os.path.join(root, ".bench_trace", cell["name"])
        self.device_trace = None

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.add_span(name, t, time.perf_counter() - t)

    def add_span(self, name: str, t0: float, dur: float) -> None:
        self.bench_spans[name] = (t0, dur)

    def setup_done(self, at: float | None = None) -> None:
        """Set-up ends, the measured window begins (now, or at `at`)."""
        self.t_setup_done = time.perf_counter() if at is None else at

    def profiler_start(self) -> float:
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return program.profiler_start(self.trace_dir)

    def profiler_stop(self, sync_perf: float, t_end: float) -> None:
        """Stop the profiler; the traced window runs from the sync
        annotation to `t_end` (perf_counter)."""
        program.profiler_stop()
        self.device_trace = {"sync_perf": sync_perf, "t_end": t_end}


def reduce_trace(ctx: RunContext, record: dict) -> dict:
    # the one reading of the run's trace; the scope readers find it here
    planes = record["scoped_planes"] = scoped_trace.load(ctx.trace_dir)
    sync, t_end = ctx.device_trace["sync_perf"], ctx.device_trace["t_end"]
    off = trace_reduce.sync_offset_ns(planes, sync)

    def to_ns(t: float) -> int:
        return int(t * 1e9) + off

    spans = [(n, to_ns(t), to_ns(t + d)) for n, t, d in record["host_spans"]]
    return trace_reduce.reduce(planes, (to_ns(sync), to_ns(t_end)), spans)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = REPO, control: bool = False, allow_cpu: bool = False,
             hooks: dict | None = None, t0: float | None = None):
    """-> (exit code, result object, the kind's full record). `allow_cpu` and `hooks` are
    the tests' (a CPU rehearsal at a tiny size; a stalled poll); `control`
    switches the configuration's lower-precision path on, to show that
    `correct` can fail."""
    t_called = time.perf_counter()
    t0 = t_called if t0 is None else t0
    reg = Registry(root)
    cell = reg.cell(workload)
    device = program.require_chip(cell["chips"], allow_cpu=allow_cpu)
    t_device = time.perf_counter()
    say(f"[device] platform {device['platform']}, kind {device['kind']}, "
        f"count {device['count']}; compile cache {device['compile_cache']}")
    say(f"[cell] {workload} = config {cell['config']['name']} x traffic "
        f"{cell['traffic']['name']} (kind {cell['traffic']['kind']}); seed "
        f"{seed}, seconds {seconds}, trace {int(trace)}"
        + (", CONTROL: " + cell["config"]["control"]["what"] if control else ""))
    ctx = RunContext(root, cell, seed, seconds, trace, control, allow_cpu,
                     hooks or {}, t0)
    ctx.add_span("imports", t0, t_called - t0)
    ctx.add_span("device_init", t_called, t_device - t_called)
    record = cell["kind"].run(ctx)
    if ctx.t_setup_done is None:
        raise RuntimeError(f"kind {cell['traffic']['kind']!r} never marked "
                           "the end of set-up")
    peak = program.memory_peak_bytes()
    record.update(bench_spans=ctx.bench_spans, setup_s=ctx.t_setup_done - t0,
                  memory_peak_bytes=peak, device_kind=device["kind"],
                  config=cell["config"], traffic=cell["traffic"])
    say(f"[run] attempted {record['attempted']}, failed {record['failed']}; "
        f"{record['groups_stepped']} of {record['groups']} groups stepped; "
        f"compiles inside the window {record['compiles_in_window']}; set-up "
        f"{record['setup_s']:.3f}s (" + ", ".join(
            f"{k} {d:.2f}" for k, (_t, d) in ctx.bench_spans.items())
        + f"); peak device memory {peak} B")

    correct, numbers, ref_s, compared_ticks = check.compare(
        cell["config"], record["sample"], record["tm_overflow"],
        record["rows_misrouted"], say=say)
    numbers.append({"name": "compiles_in_window", "limit": 0,
                    "value": record["compiles_in_window"],
                    "ok": not record["compiles_in_window"]})
    if record["compiles_in_window"]:
        say(f"[correct] FAILED: {record['compiles_in_window']} compilation(s) "
            "inside the measured window")
        correct = False
    if record["groups_stepped"] != record["groups"]:
        say(f"[run] NOTE: the window reached only {record['groups_stepped']} "
            f"of {record['groups']} resident groups")

    dev_out = {"platform": device["platform"], "kind": device["kind"],
               "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": {}, "device": dev_out,
              "reference_s": ref_s, "compared_ticks": compared_ticks}
    if not trace:
        values = dict(record["end_to_end"])
        values["setup_s"] = record["setup_s"]
        values["peak_bytes_per_stream"] = peak / record["streams"]
        for m in reg.metrics(workload, "end_to_end"):
            if m["name"] not in values:
                raise KeyError(f"cell {workload!r} did not produce end-to-end "
                               f"metric {m['name']!r}")
            result["metrics"][m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        if ctx.device_trace is None:
            raise RuntimeError("--trace 1 but the kind never ran the profiler")
        reduced = reduce_trace(ctx, record)
        record["trace"] = reduced
        dev_out["busy_s"] = reduced["busy_s"]
        dev_out["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        for m in reg.metrics(workload, "per_layer"):
            definition, reader = reg.layer_metric(m["name"])
            value = reader.read(record, definition)
            if value is not None:  # nothing to read -> left out of the line
                result["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
        say(f"[trace] window {reduced['window_s']:.3f}s, device busy "
            f"{reduced['busy_s']:.3f}s; programs {reduced['modules']}")
    result["compared"] = numbers  # each number beside its limit, last
    return 0, result, record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: run the configuration's lower-precision control "
                         "in the program's place (never part of a check)")
    a = ap.parse_args(argv)
    rc, result, _record = run_cell(a.workload, a.seed, a.seconds,
                                   bool(a.trace), control=bool(a.control),
                                   t0=_T0)
    say(json.dumps(result))
    for n in result["compared"]:
        print("[correct] " + check.describe(n), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
