"""Traffic kind `live_fields`: the product path for a model of F fields —
one record a node a slot, ``{"id", "values": [..F..], "ts"}``, ``null`` for
a metric the collector missed.

The serving, the phase lock, the drain and the accounting are kind `live`'s
own (benchmark/traffic_kinds/live.py:_serve, loaded from the cell's root and
run as it is); what differs is what is on the wire: serve's TCP listener is
built at the model's width (`n_fields`, never taken from a record), the
generator process is benchmark/generator_fields.py, and a snapshot's rows
are matched to the offered records field by field (`FieldsRecorder`): a
record is scored iff one snapshot held all F of its values, each in its own
place, NaN exactly where ``null`` was offered. Anything else a snapshot
holds — a field on another tick or in another place of the record, finite
where ``null`` was offered or the reverse — is a misrouted row."""

from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

from benchmark import program
from benchmark.feed import stream_ids
from benchmark.generator_fields import offered_records
from benchmark.registry import Registry


def fields_source(ids: list[str], n_fields: int, require_native: bool = True):
    """serve's TCP JSONL listener for records of `n_fields` values, on a
    free localhost port, started (program.tcp_source builds the scalar one
    and takes no width). A program whose listener takes no width — a commit
    before the served path carried vectors — fails here, before any state
    is made."""
    from rtap_tpu.service.sources import TcpJsonlSource

    return TcpJsonlSource(ids, port=0, native=True if require_native else None,
                          n_fields=n_fields).start()


def fields_recorder(live, cadence_s: float):
    """kind `live`'s SnapshotRecorder over records of F fields: `sent` is
    [N, S, F] and a snapshot [S, F]."""

    class FieldsRecorder(live.SnapshotRecorder):
        def _match(self, tick: int, values: np.ndarray) -> None:
            """A row with any finite field is the offered record of the
            earliest slot not yet accounted for that equals it in every
            field, NaN where ``null`` was offered; slots skipped on the way
            were overwritten before any snapshot saw them."""
            arrived = np.isfinite(values).any(axis=1)
            if not arrived.any():
                return
            # slots that can be in this snapshot: none before the earliest
            # one outstanding, none that opens (E + k * cadence) after it
            lo = int(self.next_slot.min())
            hi = min(self.N, int((self.snap_t[-1] - self.E) // cadence_s) + 1)
            sent = self.sent[lo:hi]
            same = (sent == values[None]) | (np.isnan(sent)
                                             & np.isnan(values)[None])
            k_idx = np.arange(lo, max(lo, hi))[:, None]
            hit = same.all(axis=2) & (k_idx >= self.next_slot[None, :])
            found = hit.any(axis=0)
            slot = hit.argmax(axis=0) + lo
            cols = np.nonzero(arrived & found)[0]
            self.scored_tick[slot[cols], cols] = tick
            self.next_slot[cols] = slot[cols] + 1
            self.misrouted += int((arrived & ~found).sum())

    return FieldsRecorder


def run(ctx) -> dict:
    traffic, layout = ctx.traffic, ctx.config["layout"]
    cadence, guard = traffic["cadence_s"], traffic["guard_s"]
    if ctx.config.get("live_cadence_s") != cadence:
        raise ValueError(
            f"traffic {traffic['name']!r} runs at {cadence} s; configuration "
            f"{ctx.config['name']!r} states live_cadence_s "
            f"{ctx.config.get('live_cadence_s')!r}")
    if traffic["phase_spread_s"] + 2 * guard > cadence + 1e-9:
        raise ValueError("phase_spread_s + 2 * guard_s must fit in a cadence")
    NG, G = layout["groups"], layout["group_size"]
    S = NG * G
    N = int(ctx.seconds // cadence)
    if N < 1:
        raise ValueError(f"--seconds {ctx.seconds} holds no {cadence} s slot")
    seed = ctx.seed
    cfg = program.model_config(ctx.config, control=ctx.control)
    F = cfg.n_fields
    sent, phi, _send = offered_records(
        seed, S, N, F, traffic["null_share"], traffic["phase_spread_s"],
        traffic["send_quantum_s"])
    # kind `live`'s serving and accounting, from the cell's own root
    live = Registry(ctx.root)._module("traffic_kinds", "live")
    live.SnapshotRecorder = fields_recorder(live, cadence)

    # the listener first (a program that cannot carry vectors ends here);
    # its ids are put in serve's dispatch order once the registry has it
    t_state = time.perf_counter()
    tcp = fields_source(stream_ids(S), F, require_native=not ctx.allow_cpu)
    gen = None
    try:
        registry, ids = program.build_registry(cfg, NG, G, seed)
        if ids != tcp.stream_ids:
            tcp.set_ids(ids)
        ctx.add_span("state", t_state, time.perf_counter() - t_state)
        with ctx.span("generator_start"):
            env = dict(os.environ, PYTHONPATH=os.pathsep.join(
                [ctx.root, os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
            gen = subprocess.Popen(
                [sys.executable, "-m", "benchmark.generator_fields",
                 "--fields", str(F), "--null-share", str(traffic["null_share"]),
                 "--port", str(tcp.address[1]), "--seed", str(seed),
                 "--streams", str(S), "--slots", str(N),
                 "--cadence", str(cadence),
                 "--spread", str(traffic["phase_spread_s"]),
                 "--quantum", str(traffic["send_quantum_s"]),
                 "--ts-base", str(traffic["row_ts_base"]),
                 "--hold", str(int(traffic["hold_until_snapshot"]))],
                cwd=ctx.root, env=env, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True)
            if gen.stdout.readline().strip() != "READY":
                raise RuntimeError("the generator process did not come up")
        record = live._serve(ctx, registry, tcp, gen, sent, phi, N)
        ctx.say(f"[live_fields] {F} values a record: the listener wrote "
                f"{tcp.values_parsed} values and took {tcp.values_null} nulls; "
                f"offered {int(np.isfinite(sent).sum())} and "
                f"{int(np.isnan(sent).sum())}")
        return record
    finally:
        if gen is not None:
            try:
                gen.stdin.write("STOP\n")
                gen.stdin.flush()
            except (BrokenPipeError, ValueError, OSError):
                pass
            try:
                gen.wait(timeout=10)
            except subprocess.TimeoutExpired:
                gen.kill()
                gen.wait()
        tcp.close()
