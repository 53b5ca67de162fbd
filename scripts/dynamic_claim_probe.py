"""On-silicon probe for dynamic slot claims (SURVEY.md C19 lazy creation).

The CPU test suite pins claim semantics bit-exactly; this validates the
DEVICE path on the real chip: `set_state_row`'s donated .at[slot].set
update against grouped TPU state, scoring continuity after a mid-run
claim, and the claimed slot's post-probation emergence. Runs in seconds;
queued as a harvest step so the feature is silicon-proven, not just
CPU-proven.

    python scripts/dynamic_claim_probe.py [--group-size 256] [--ticks 48]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rtap_tpu.utils.platform import require_device  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--group-size", type=int, default=256)
    ap.add_argument("--ticks", type=int, default=48)
    args = ap.parse_args()

    require_device()  # no TPU and no explicit CPU choice -> fail here
    import jax

    from rtap_tpu.config import scaled_cluster_preset
    from rtap_tpu.service.registry import StreamGroupRegistry

    platform = jax.devices()[0].platform
    cfg = scaled_cluster_preset(32)
    n_live = args.group_size - 2  # leave claimable pads
    reg = StreamGroupRegistry(cfg, group_size=args.group_size, backend="tpu")
    for i in range(n_live):
        reg.add_stream(f"s{i}")
    reg.finalize()
    grp = reg.groups[0]

    rng = np.random.default_rng(3)

    def tick(k: int) -> np.ndarray:
        vals = (30 + 5 * rng.random(grp.G)).astype(np.float32)
        raw, _, _ = grp.run_chunk(
            vals[None, :], np.full((1, grp.G), 1_700_000_000 + k, np.int64))
        return raw[0]

    for k in range(args.ticks):
        tick(k)

    # snapshot a pad slot's state row, claim it, verify the row was reset
    pad_slot = grp.live_slots()[-1] + 1 if n_live else 0
    before = {k: np.asarray(v)[pad_slot].copy() for k, v in grp.state.items()}
    reg.add_stream("claimed")
    _, slot = reg.lookup("claimed")
    assert slot == pad_slot, (slot, pad_slot)
    after = {k: np.asarray(v)[slot] for k, v in grp.state.items()}
    from rtap_tpu.models.state import init_state

    fresh = init_state(cfg, grp.seed)
    reset_exact = all(
        np.array_equal(after[k], np.asarray(fresh[k]).astype(after[k].dtype))
        for k in after)
    changed = any(not np.array_equal(before[k], after[k]) for k in before)

    raws = [tick(args.ticks + j) for j in range(args.ticks)]
    finite = all(np.isfinite(r).all() for r in raws)

    out = {
        "platform": platform,
        "group_size": args.group_size,
        "claimed_slot": int(slot),
        "reset_matches_fresh_init": bool(reset_exact),
        "pad_state_was_mutated_by_claim": bool(changed),
        "post_claim_scores_finite": bool(finite),
        "ok": bool(reset_exact and finite),
    }
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
