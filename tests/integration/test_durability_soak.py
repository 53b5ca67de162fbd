"""ISSUE 5 acceptance: crash-consistent durability, end to end.

Three drills against REAL process deaths (never mocks):

1. the 2-kill crash soak smoke — ``scripts/crash_soak.py`` SIGKILLs a
   journaled+checkpointed serve child twice at seeded journal-observed
   ticks under the real Supervisor, and its own verdict machinery proves
   final state bit-identical to the fault-free run with the alert stream
   exactly-once (zero duplicated / zero lost ``alert_id``s);
2. the supervised chaos soak — a seeded ``proc_exit`` fault (abrupt
   ``os._exit`` at a tick boundary) plus in-process faults, restarted by
   the Supervisor, journal recovery verified on the incident stream;
3. the checkpoint-save-residue x journal interplay — a child killed
   MID-CHECKPOINT (the state tree landed in the temp sibling, meta.json
   never did) resumes from the rolled-back previous checkpoint with a
   LONGER journal replay, still bit-identical and exactly-once.

Tiny configs + CPU-oracle backend keep each drill in seconds; quick tier.
"""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _env():
    env = {**os.environ, "RTAP_FORCE_CPU": "1"}
    return env


def test_crash_soak_two_kills_is_exactly_once(tmp_path):
    """The in-tree acceptance smoke: K=2 SIGKILLs; the soak's exit code
    IS the verdict (5 = durability violated)."""
    out = str(tmp_path / "report.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "crash_soak.py"),
         "--seed", "11", "--kills", "2", "--streams", "6",
         "--group-size", "3", "--ticks", "72", "--cadence", "0.005",
         "--checkpoint-every", "7", "--backend", "cpu",
         "--workdir", str(tmp_path / "w"), "--out", out],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"crash soak failed rc={proc.returncode}\n{proc.stderr[-3000:]}"
    report = json.load(open(out))
    assert report["verified"], report["failures"]
    assert report["deaths"] == 2
    assert report["kill_signals"] == [9, 9]
    assert report["duplicated"] == 0 and report["lost"] == 0
    assert report["alert_ids"] > 0
    assert report["state_leaves_compared"] > 0
    assert report["total_ticks_completed"] == 72
    # at least the final (completing) child replayed journal ticks
    assert any(c["replayed_ticks"] > 0 for c in report["catch_up"])


def test_chaos_soak_supervised_proc_exit(tmp_path):
    """Satellite: ChaosSpec's proc_exit kind under chaos_soak --supervise
    — the seeded abrupt death fires exactly once across restarts, the
    run completes its total budget, and journal recovery ran."""
    out = str(tmp_path / "report.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "chaos_soak.py"),
         "--supervise", "--seed", "4", "--kills", "1", "--streams", "6",
         "--group-size", "3", "--ticks", "48", "--cadence", "0.005",
         "--checkpoint-every", "8", "--backend", "cpu", "--rate", "0.06",
         "--workdir", str(tmp_path / "w"), "--out", out],
        env=_env(), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"supervised chaos soak failed rc={proc.returncode}\n" \
        f"{proc.stderr[-3000:]}"
    report = json.load(open(out))
    assert report["verified"], report["failures"]
    assert report["deaths"] == 1
    assert report["ticks_completed"] == 48
    assert report["journal_replay_events"] >= 1
    assert report["duplicated"] == 0


# ---- drill 3: kill DURING a checkpoint round -------------------------

N_STREAMS = 4
GROUP_SIZE = 2
TOTAL = 40
CK_EVERY = 6
SEED = 5

_CHILD = r"""
import os, sys
sys.path.insert(0, {repo!r})
from rtap_tpu.utils.platform import maybe_force_cpu
maybe_force_cpu()
import numpy as np
from rtap_tpu.config import cluster_preset
from rtap_tpu.resilience import TickJournal
from rtap_tpu.service import checkpoint
from rtap_tpu.service.loop import live_loop
from rtap_tpu.service.registry import StreamGroupRegistry

# die during the SECOND periodic checkpoint round, group0: the state
# tree has landed in the temp sibling but meta.json (the completeness
# marker) never will — then the process dies mid-save. On disk: the
# previous (tick-6) checkpoint intact + an incomplete .tmp residue.
calls = [0]
_orig = checkpoint.save_group
def dying_save(grp, path, **kw):
    calls[0] += 1
    if calls[0] == 3:
        import uuid
        from pathlib import Path
        p = Path(path).absolute()
        tmp = p.parent / (".{{}}.tmp-{{}}".format(p.name, uuid.uuid4().hex[:8]))
        (tmp / "state").mkdir(parents=True)
        os._exit(9)  # no atexit, no flush: a genuine crash
    return _orig(grp, path, **kw)
checkpoint.save_group = dying_save

def source(k):
    rng = np.random.Generator(np.random.Philox(key=({seed}, k)))
    return (30 + 5 * rng.random({n})).astype(np.float32), 1_700_000_000 + k

reg = StreamGroupRegistry(cluster_preset(), group_size={gs}, backend="cpu",
                          threshold=-1e9, debounce=1)
for i in range({n}):
    reg.add_stream("s%d" % i)
reg.finalize()
j = TickJournal({jdir!r})
live_loop(source, reg, n_ticks={total}, cadence_s=0.0, alert_path={alerts!r},
          checkpoint_dir={ckdir!r}, checkpoint_every={ck}, journal=j)
raise SystemExit("unreachable: the dying save must fire")
"""


def _mkreg():
    from rtap_tpu.config import cluster_preset
    from rtap_tpu.service.registry import StreamGroupRegistry

    reg = StreamGroupRegistry(cluster_preset(), group_size=GROUP_SIZE,
                              backend="cpu", threshold=-1e9, debounce=1)
    for i in range(N_STREAMS):
        reg.add_stream(f"s{i}")
    reg.finalize()
    return reg


def _feed(base=0):
    def source(k):
        g = base + k
        rng = np.random.Generator(np.random.Philox(key=(SEED, g)))
        return (30 + 5 * rng.random(N_STREAMS)).astype(np.float32), \
            1_700_000_000 + g
    return source


def _group_fingerprint(grp):
    out = {"ticks": grp.ticks, "alert_run": np.asarray(grp._alert_run)}
    for g, st in enumerate(grp._states):
        for k, v in st.items():
            out[f"s{g}/{k}"] = np.asarray(v)
    for k, v in grp.likelihood.state_dict().items():
        out[f"lik/{k}"] = np.asarray(v)
    return out


def _alert_records(path):
    recs = {}
    for line in open(path):
        if line.startswith('{"event"'):
            continue
        d = json.loads(line)
        assert d["alert_id"] not in recs, f"duplicate {d['alert_id']}"
        recs[d["alert_id"]] = d
    return recs


def test_kill_during_checkpoint_round_resumes_from_rolled_back(tmp_path):
    from rtap_tpu.resilience import TickJournal
    from rtap_tpu.service.loop import live_loop

    jdir = str(tmp_path / "journal")
    ckdir = str(tmp_path / "ck")
    alerts = str(tmp_path / "alerts.jsonl")

    # 1. the doomed run, in its own process — killed mid-save
    child = _CHILD.format(repo=REPO, seed=SEED, n=N_STREAMS, gs=GROUP_SIZE,
                          total=TOTAL, ck=CK_EVERY, jdir=jdir,
                          alerts=alerts, ckdir=ckdir)
    proc = subprocess.run([sys.executable, "-c", child], env=_env(),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 9, \
        f"dying save did not fire: rc={proc.returncode}\n" \
        f"{proc.stderr[-2000:]}"
    # the rolled-back state: both groups' checkpoints at the FIRST round
    meta = json.load(open(os.path.join(ckdir, "group0000", "meta.json")))
    assert meta["ticks"] == CK_EVERY
    assert meta["journal_tick"] == CK_EVERY  # global == group tick here
    assert "alerts_offset" in meta
    residue = glob.glob(os.path.join(ckdir, ".group0000.tmp-*"))
    assert residue, "the interrupted save left no temp-sibling residue"

    # 2. resume in-process: rolled-back checkpoint + LONGER journal replay
    j = TickJournal(jdir)
    base = j.next_tick
    assert base == 2 * CK_EVERY  # the killing round's ticks are journaled
    reg = _mkreg()
    stats = live_loop(_feed(base), reg, n_ticks=TOTAL - base, cadence_s=0.0,
                      alert_path=alerts, checkpoint_dir=ckdir,
                      checkpoint_every=CK_EVERY, journal=j)
    j.close()
    # the replay spans checkpoint tick 6 .. journal tick 11 — the whole
    # post-rollback window, not just the save round
    assert stats["journal"]["replayed_ticks"] == CK_EVERY
    # every replayed alert was already delivered by the dead run
    # (flush-per-batch): all suppressed, none duplicated
    assert stats["journal"]["suppressed_alerts"] == CK_EVERY * N_STREAMS

    # 3. bit-identical to an uninterrupted run over the same feed
    ref_alerts = str(tmp_path / "ref_alerts.jsonl")
    ref = _mkreg()
    live_loop(_feed(0), ref, n_ticks=TOTAL, cadence_s=0.0,
              alert_path=ref_alerts)
    for grp, rgrp in zip(reg.groups, ref.groups):
        got, want = _group_fingerprint(grp), _group_fingerprint(rgrp)
        assert sorted(got) == sorted(want)
        for k in want:
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(want[k]), err_msg=k)
    got_recs = _alert_records(alerts)
    want_recs = _alert_records(ref_alerts)
    assert got_recs == want_recs  # exactly-once AND content-identical

    # 4. the incomplete residue was swept by the resume's first good save
    assert not glob.glob(os.path.join(ckdir, ".group0000.tmp-*"))
