"""Score the full NAB stand-in corpus and commit the result as an artifact.

Runs the detector over every file of the stand-in corpus (8 files, 5 metric
profiles — data/nab_corpus.STANDIN_FILES) through the full NAB machinery
(per-file detection -> threshold sweep -> scaled-sigmoid window scoring ->
normalization) and writes reports/nab_standin.json with per-profile scores.

The stand-in is NOT the real NAB corpus (absent in this offline environment
— SURVEY.md §6 blocker); its absolute scores are not comparable to the
public scoreboard. What the artifact pins is (a) the full pipeline runs
corpus-scale end to end, and (b) a quality reference point that future
rounds must not regress (integration floors live in
tests/integration/test_nab_run.py).

    RTAP_FORCE_CPU=1 python scripts/nab_standin_report.py [--processes 1]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import maybe_force_cpu  # noqa: E402

maybe_force_cpu()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--processes", type=int, default=1)
    ap.add_argument("--backend", default="tpu", choices=("tpu", "cpu"),
                    help="tpu = all files as ONE vmapped device group "
                         "(detect_files_batched; ~minutes on a real chip). "
                         "cpu = one oracle per file (hours at NAB-preset "
                         "size on a 1-core host — use --rows to shrink)")
    ap.add_argument("--rows", type=int, default=None,
                    help="truncate every file to this many rows (cheap drives)")
    ap.add_argument("--columns", type=int, default=None,
                    help="run the width-scaled NAB model "
                         "(config.scaled_nab_preset) instead of the full "
                         "2048-column preset — the model-width study's "
                         "generalization question, and the config that makes "
                         "the CPU corpus run feasible (~columns/2048 of the "
                         "full model's 10.5 s/tick)")
    ap.add_argument("--out", default=None,
                    help="default reports/nab_standin.json, or "
                         "nab_standin_cols<N>.json when --columns is set "
                         "(the full-size on-device artifact must not be "
                         "silently overwritten by a scaled run)")
    args = ap.parse_args()
    if args.out is None:
        name = (f"nab_standin_cols{args.columns}.json" if args.columns
                else "nab_standin.json")
        args.out = os.path.join(REPO, "reports", name)

    device = None
    if args.backend == "tpu":
        from rtap_tpu.utils.platform import enable_compile_cache, require_device

        # no TPU and no explicit CPU choice -> fail here. On the device path
        # everything runs in THIS process (--processes only fans out the
        # cpu oracle), so one process holds the chip.
        device = require_device()
        # the NAB-preset programs are the repo's biggest compiles (65k-cell
        # TM); a retry must not re-pay them
        enable_compile_cache()

    from rtap_tpu.data.nab_corpus import NabFile, ensure_standin_corpus, load_corpus
    from rtap_tpu.nab.runner import run_corpus

    cfg = None
    if args.columns:
        from rtap_tpu.config import scaled_nab_preset

        # the runner rescales only the encoder resolution per file on top
        # of this base (nab/runner._file_range_config), same as full-size
        cfg = scaled_nab_preset(args.columns)

    with tempfile.TemporaryDirectory() as td:
        root = ensure_standin_corpus(td)
        files = load_corpus(root)
        if args.rows:
            files = [NabFile(f.name, f.timestamps[: args.rows], f.values[: args.rows],
                             f.windows) for f in files]
        t0 = time.time()
        res = run_corpus(files, cfg=cfg, backend=args.backend,
                         processes=args.processes)
        wall = time.time() - t0

    # the oracle path is numpy-only and must never bring a backend up (it
    # would claim the chip after an hours-long CPU run, and mislabel it)
    platform = device["platform"] if device else "host-oracle"

    from rtap_tpu.config import nab_preset

    report = {
        "corpus": "stand-in (deterministic synthetic, NAB on-disk format)",
        "backend": args.backend,
        "platform": platform,
        "device_kind": device["kind"] if device else None,
        "device_count": device["count"] if device else None,
        "columns": (cfg if cfg is not None else nab_preset()).sp.columns,
        "files": [f.name for f in files],
        "records": int(sum(len(f.values) for f in files)),
        "wall_s": round(wall, 1),
        "scores": {
            prof: {"threshold": round(thr, 4), "score": round(score, 2)}
            for prof, (thr, score) in res.scores.items()
        },
        "note": (
            "Stand-in corpus scores are not comparable to the public NAB "
            "scoreboard; they pin the pipeline end-to-end and guard "
            "regressions. Real-corpus swap-in: set RTAP_NAB_CORPUS."
        ),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report["scores"]))


if __name__ == "__main__":
    main()
