"""Multi-host (DCN) smoke test — SURVEY.md §2.4's distributed backend.

The reference scales across hosts by share-nothing OS processes; our analog
is jax.distributed over DCN with the same stream-axis sharding code as the
single-host ICI path. This test launches TWO real processes (one per fake
"host", 2 virtual CPU devices each), initializes the jax.distributed
coordinator via rtap_tpu.parallel.init_distributed, and steps a sharded
stream group end to end on the 4-device global mesh — pinning that
init_distributed, put_sharded (make_array_from_callback across processes),
shard_state, and sharded_chunk_step all work multi-process, not just
single-process.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

WORKER = Path(__file__).parent / "dcn_worker.py"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_dcn_smoke():
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    repo_root = str(Path(__file__).resolve().parents[2])
    inherited = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([repo_root, *inherited])
    procs = [
        subprocess.Popen(
            [sys.executable, str(WORKER), coordinator, "2", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"DCN_OK p{pid}" in out, out
