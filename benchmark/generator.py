"""The live traffic generator: a process of its own, on a fixed schedule.

    python -m benchmark.generator --port P --seed N --streams S --slots K \
        --cadence C --spread W --quantum Q --ts-base B

It never imports JAX or the program. It connects to the listener, builds
every payload of the run from the seed (benchmark.feed.live_rows), prints
READY, and waits on stdin for ``E <t>``: the CLOCK_MONOTONIC instant
(time.perf_counter of the measuring process — the same clock on Linux) at
which slot 0 opens. Stream i's row of slot k is due at E + k*C + phi[i] and
goes on the wire at E + k*C + send[i] — a fixed schedule, open loop — unless
--hold 1 keeps it back until the server has taken a snapshot since the
previous slot (class Gate); every row's lateness counts from its due time
either way. After the last row it prints one JSON report line (what was sent,
when, how late) and exits when told STOP. Rows before slot 0 or after slot
K-1 do not exist."""

from __future__ import annotations

import argparse
import json
import socket
import sys
import threading
import time

import numpy as np

from benchmark.feed import live_rows, stream_ids


def build_payloads(seed: int, n_streams: int, n_slots: int, spread: float,
                   quantum: float, ts_base: int):
    """-> (offsets [B] ascending send offsets within a slot,
    payloads [n_slots][B] bytes, rows [B] row count per batch,
    phi [n_streams], batch_of [n_streams])."""
    values, phi, send = live_rows(seed, n_streams, n_slots, spread, quantum)
    offsets, batch_of = np.unique(send, return_inverse=True)
    order = np.argsort(batch_of, kind="stable")
    bounds = np.searchsorted(batch_of[order], np.arange(len(offsets) + 1))
    prefixes = [f'{{"id": "{sid}", "value": ' for sid in stream_ids(n_streams)]
    payloads = []
    for k in range(n_slots):
        suffix = f', "ts": {ts_base + k}}}\n'
        vals = values[k].astype(float).tolist()
        lines = [prefixes[i] + repr(vals[i]) + suffix for i in order]
        payloads.append([
            "".join(lines[bounds[b]:bounds[b + 1]]).encode()
            for b in range(len(offsets))])
    return offsets, payloads, np.diff(bounds), phi, batch_of


class Gate:
    """What the measuring process says on stdin, read beside the sending:
    ``S <t>`` — the loop took a snapshot at perf_counter t — and ``STOP``.

    With --hold, a slot's rows stay off the wire until a snapshot has been
    taken after the previous slot's last row went out, so that no stream ever
    has two rows between two snapshots (the snapshot-and-drain source would
    overwrite the first). On schedule that snapshot comes a whole guard before
    the slot's first row is due and the gate holds nothing back; when the
    server's phase has slipped, rows wait, and their lateness is counted
    against the server, from their due time."""

    def __init__(self, stdin):
        self.cond = threading.Condition()
        self.latest_snapshot = -1e300
        self.stopped = False
        self._thread = threading.Thread(target=self._read, args=(stdin,),
                                        daemon=True, name="generator-stdin")
        self._thread.start()

    def _read(self, stdin) -> None:
        for line in stdin:
            word, _, rest = line.partition(" ")
            with self.cond:
                if word == "S":
                    self.latest_snapshot = max(self.latest_snapshot, float(rest))
                else:  # STOP, or anything unknown: end the run
                    self.stopped = True
                self.cond.notify_all()
            if self.stopped:
                return
        with self.cond:  # EOF: the measuring process is gone
            self.stopped = True
            self.cond.notify_all()

    def wait_snapshot_after(self, t: float) -> bool:
        """Block until a snapshot later than `t` is known -> False if the
        run was stopped first."""
        with self.cond:
            while self.latest_snapshot <= t and not self.stopped:
                self.cond.wait()
            return self.latest_snapshot > t

    def wait_stop(self) -> None:
        with self.cond:
            while not self.stopped:
                self.cond.wait()


#: a snapshot counts as "after" a row only this long after the row went out
#: (the listener's parser has to have seen it)
SETTLE_S = 0.1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--streams", type=int, required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--cadence", type=float, required=True)
    ap.add_argument("--spread", type=float, required=True)
    ap.add_argument("--quantum", type=float, required=True)
    ap.add_argument("--ts-base", type=int, required=True)
    ap.add_argument("--hold", type=int, choices=(0, 1), required=True)
    a = ap.parse_args(argv)
    offsets, payloads, rows, phi, batch_of = build_payloads(
        a.seed, a.streams, a.slots, a.spread, a.quantum, a.ts_base)
    with socket.create_connection(("127.0.0.1", a.port), timeout=30.0) as sock:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        print("READY", flush=True)
        word, _, rest = sys.stdin.readline().partition(" ")
        if word != "E":
            return 2  # the harness gave up before the run began
        E = float(rest)
        if not -60.0 < E - time.perf_counter() < 60.0:
            print(json.dumps({"error": "the two processes' monotonic clocks "
                              f"disagree: E={E}, here {time.perf_counter()}"}),
                  flush=True)
            return 2
        gate = Gate(sys.stdin)
        sent_at = np.full((a.slots, len(offsets)), np.nan)
        held = 0  # batches the gate kept back past their time
        last_out = -1e300
        for k in range(a.slots):
            base = E + k * a.cadence
            if a.hold and k and not gate.wait_snapshot_after(last_out + SETTLE_S):
                break
            # batches whose time had come while the gate was still shut
            held += int(np.sum(base + offsets < time.perf_counter() - 0.002))
            for b, off in enumerate(offsets):
                wait = base + off - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if gate.stopped:
                    break
                sent_at[k, b] = time.perf_counter()
                sock.sendall(payloads[k][b])
            last_out = time.perf_counter()
        # each row's lateness: on the wire minus due (NaN: never sent)
        due = (E + a.cadence * np.arange(a.slots)[:, None] + phi[None, :])
        late = (sent_at[:, batch_of] - due).ravel()
        late = late[np.isfinite(late)]
        print(json.dumps({
            "rows_sent": int((rows[None, :] * np.isfinite(sent_at)).sum()),
            "E": E, "batches_held": held,
            "late_ms_p50": float(np.percentile(late, 50) * 1e3),
            "late_ms_p95": float(np.percentile(late, 95) * 1e3),
            "late_ms_max": float(late.max() * 1e3),
            "first_send": sent_at[:, 0].tolist(),
            "last_send": sent_at[:, -1].tolist(),
            "sent_at": sent_at.tolist(),
        }).replace("NaN", "null"), flush=True)
        gate.wait_stop()  # only now close the connection
    return 0


if __name__ == "__main__":
    sys.exit(main())
