"""Service-level crash/resume drill (SURVEY.md §4 item 3, §5 checkpoint/
resume as elastic recovery; round-3 verdict missing #6).

A real subprocess runs a grouped replay with periodic atomic checkpoints and
is KILLED abruptly mid-stream (os._exit — no cleanup, no flush: the honest
crash). The parent then resumes the replay from the surviving checkpoint
directory and asserts the resumed tail scores are bit-identical to an
uninterrupted reference run — proving recovery end-to-end through the
registry, device state, and the sequential likelihood ring, not just the
state-dict round trip of tests/unit/test_checkpoint.py.
"""

import json
import os
import subprocess
import sys

import numpy as np

from rtap_tpu.config import cluster_preset
from rtap_tpu.data.synthetic import SyntheticStreamConfig, generate_cluster
from rtap_tpu.service.loop import replay_streams

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_NODES = 2  # x3 metrics = 6 streams
LENGTH = 640
CHUNK = 64

_CHILD = r"""
import os, sys
sys.path.insert(0, {repo!r})
from rtap_tpu.utils.platform import maybe_force_cpu
maybe_force_cpu()

from rtap_tpu.config import cluster_preset
from rtap_tpu.data.synthetic import SyntheticStreamConfig, generate_cluster
from rtap_tpu.service.loop import replay_streams
from rtap_tpu.service import registry

# crash injection: die abruptly right after the 6th collected chunk — two
# chunks past the checkpoint_every=4 save, so real scored progress is lost
# and resume MUST come from the checkpoint, not from luck
_collected = [0]
_orig = registry.StreamGroup.collect_chunk
def _dying_collect(self, handle):
    out = _orig(self, handle)
    _collected[0] += 1
    if _collected[0] == 6:
        os._exit(9)  # no atexit, no flush: a genuine crash
    return out
registry.StreamGroup.collect_chunk = _dying_collect

streams = generate_cluster({n_nodes}, cfg=SyntheticStreamConfig(
    length={length}, cadence_s=1.0, noise_phi=0.97, noise_scale=0.5), seed=7)
replay_streams(streams, cluster_preset(), backend="tpu", chunk_ticks={chunk},
               checkpoint_dir={ckdir!r}, checkpoint_every=4)
raise SystemExit("unreachable: the crash hook must fire")
"""


def test_crash_mid_replay_resumes_bit_identically(tmp_path):
    ckdir = str(tmp_path / "ck")
    scfg = SyntheticStreamConfig(length=LENGTH, cadence_s=1.0,
                                 noise_phi=0.97, noise_scale=0.5)
    streams = generate_cluster(N_NODES, cfg=scfg, seed=7)

    # 1. uninterrupted reference, same inputs
    ref = replay_streams(streams, cluster_preset(), backend="tpu", chunk_ticks=CHUNK)

    # 2. the doomed run, in its own process
    child = _CHILD.format(repo=REPO, n_nodes=N_NODES, length=LENGTH,
                          chunk=CHUNK, ckdir=ckdir)
    env = {**os.environ, "RTAP_FORCE_CPU": "1"}
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 9, f"crash hook did not fire: rc={proc.returncode}\n{proc.stderr[-2000:]}"
    assert os.path.isdir(os.path.join(ckdir, "group0000")), "no checkpoint survived the crash"
    meta = json.loads(open(os.path.join(ckdir, "group0000", "meta.json")).read())
    assert 0 < meta["ticks"] < LENGTH, meta["ticks"]  # mid-stream, not done

    # 3. resume from the surviving checkpoint; only the tail is recomputed
    res = replay_streams(streams, cluster_preset(), backend="tpu", chunk_ticks=CHUNK,
                         checkpoint_dir=ckdir, checkpoint_every=4)
    boundary = res.throughput["resumed_from"]["group0"]
    assert boundary == meta["ticks"]
    assert np.isnan(res.raw[:boundary]).all()  # scored by the killed run, not re-run

    # 4. the resumed tail is bit-identical to the uninterrupted reference —
    # through raw scores, the likelihood ring, and alert decisions
    np.testing.assert_array_equal(res.raw[boundary:], ref.raw[boundary:])
    np.testing.assert_array_equal(
        res.log_likelihood[boundary:], ref.log_likelihood[boundary:]
    )
    np.testing.assert_array_equal(res.alerts[boundary:], ref.alerts[boundary:])
