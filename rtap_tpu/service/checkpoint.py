"""Checkpoint / resume for stream groups (SURVEY.md §5 "Checkpoint/resume").

The reference saves model state via NuPIC's Cap'n Proto serialization
(`model.save()` / `ModelFactory.loadFromCheckpoint`), and the anomaly
-likelihood history must ride along or likelihoods reset. Here a checkpoint
is the group's full resume state: the device state pytree (fetched to host),
the batched-likelihood state, stream ids, tick count, and the model config —
written atomically per group with orbax. A resumed group continues
bit-identically to an uninterrupted run (tests/unit/test_checkpoint.py).
"""

from __future__ import annotations

import json
import shutil
import time
import uuid
from pathlib import Path

import numpy as np

from rtap_tpu.config import ModelConfig
from rtap_tpu.obs import get_registry
from rtap_tpu.obs.trace import span
from rtap_tpu.service.registry import StreamGroup


def _tree_bytes(tree) -> int:
    """Bytes of every array leaf of a (nested) state tree."""
    if isinstance(tree, dict):
        return sum(_tree_bytes(v) for v in tree.values())
    return int(np.asarray(tree).nbytes)


def _count_bytes(n: int, op: str) -> None:
    get_registry().counter(
        "rtap_obs_checkpoint_bytes_total",
        "bytes of group state trees written to (save) or read from (load) "
        "checkpoints", op=op).inc(n)


def save_group(grp: StreamGroup, path: str | Path,
               alerts_offset: int | None = None,
               journal_tick: int | None = None, trace=None,
               predict_state: dict | None = None) -> None:
    """Write one group's resume state to `path` (a directory, per group).

    Atomic on overwrite: the tree + meta are written to a fresh temp sibling
    directory and swapped in with renames, so a crash mid-save can never leave
    a directory that has meta.json (the completeness marker) but a partially
    rewritten state tree.

    `alerts_offset` is the alert-delivery cursor (ISSUE 5): the alert
    sink's byte size at this save instant. Saves happen with the
    pipeline fully drained and the sink flushed, so every alert for
    ticks <= this checkpoint's `ticks` sits BEFORE the cursor and every
    byte past it belongs to post-checkpoint ticks — on resume, the
    journal replay scans the sink from the cursor and suppresses exactly
    the already-delivered alert ids (exactly-once across a crash;
    docs/RESILIENCE.md durability section).

    `journal_tick` is the GLOBAL journal tick cursor at this save
    instant. It equals `ticks` on a group's original timeline, but a
    mid-run quarantine restore REWINDS the group counter while the
    global clock keeps running — the journal replay must match rows by
    this global cursor, never by the rewindable per-group one.

    `predict_state` is the predictive tracker's part of the group
    (`PredictTracker.group_state`: the paging rule's latches, the fuser's
    open windows), saved beside the debounce counters; a loaded group hands
    it back as `resume_predict_state` and the resuming loop gives it to its
    own tracker, so a restarted fleet pages from where it stood.

    One `rtap.checkpoint.save` span (obs/trace.py; into `trace`'s ring too
    where a recorder is handed over) covers the whole save: `group`, `bytes`.
    """
    sp = span("rtap.checkpoint.save", trace, group=grp.stream_ids[0]).begin()
    try:
        n = _save_group(grp, path, alerts_offset, journal_tick, predict_state)
    except BaseException:
        sp.end(record=False)
        raise
    sp.end(bytes=n)


# rtap: host-boundary — checkpoint save OWNS the device->host
# materialization: it must fetch the full (possibly mesh-sharded) tree
# to write a topology-independent checkpoint, with the pipeline drained
def _save_group(grp, path, alerts_offset, journal_tick,
                predict_state=None) -> int:
    """save_group's body -> the bytes of the state tree it wrote."""
    import jax
    import orbax.checkpoint as ocp

    obs = get_registry()
    t_save = time.perf_counter()

    path = Path(path).absolute()
    if grp.backend == "tpu":
        from rtap_tpu.ops.resident import host_public

        # the files keep the public layout: the leaves are fetched as the
        # device holds them and re-laid on the host
        tree = {"model": host_public(jax.device_get(grp.resident), grp.cfg.tm, grp)}
    else:
        # per-stream state dicts include classifier cls_* arrays when enabled
        # (the oracle operates on the shared state layout, like TMOracle)
        tree = {"model": {f"s{g}": grp._states[g] for g in range(grp.G)}}
    tree["likelihood"] = grp.likelihood.state_dict()
    tree["alert_run"] = np.asarray(grp._alert_run)  # debounce counters
    if predict_state is not None:
        tree["predict_latches"] = {
            k: np.asarray(v) for k, v in predict_state["latches"].items()}

    meta = {
        "backend": grp.backend,
        "stream_ids": grp.stream_ids,
        "ticks": grp.ticks,
        "threshold": grp.threshold,
        "debounce": grp.debounce,
        "predict": int(getattr(grp, "predict", 0)),
        "n_live": getattr(grp, "n_live", grp.G),
        "sharded": grp.mesh is not None,
        "config": grp.cfg.to_dict(),
        "alert_epoch": int(getattr(grp, "alert_epoch", 0)),
    }
    if alerts_offset is not None:
        meta["alerts_offset"] = int(alerts_offset)
    if journal_tick is not None:
        meta["journal_tick"] = int(journal_tick)
    if predict_state is not None and predict_state.get("blast") is not None:
        meta["predict_blast"] = predict_state["blast"]
    tmp = path.parent / f".{path.name}.tmp-{uuid.uuid4().hex[:8]}"
    swapped = False
    try:
        tmp.mkdir(parents=True)
        with ocp.PyTreeCheckpointer() as ckptr:
            ckptr.save(tmp / "state", tree, force=True)
        # meta written AFTER the tree: its presence marks the checkpoint complete
        (tmp / "meta.json").write_text(json.dumps(meta))
        if path.exists():
            old = path.parent / f".{path.name}.old-{uuid.uuid4().hex[:8]}"
            path.rename(old)
            try:
                tmp.rename(path)
                swapped = True
            except BaseException:
                old.rename(path)  # roll the previous checkpoint back in place
                raise
            shutil.rmtree(old, ignore_errors=True)
        else:
            tmp.rename(path)
            swapped = True
    except BaseException:
        # a failed save must leave the previous checkpoint intact (the
        # whole write happened in the temp sibling; the finally below
        # sweeps it) AND be visible: live_loop turns this into a
        # checkpoint_save_failed event and its breaker decides whether to
        # keep trying — a full disk must never kill scoring
        obs.counter(
            "rtap_obs_checkpoint_save_failures_total",
            "group checkpoint saves that raised before landing (previous "
            "checkpoint left intact)").inc()
        raise
    finally:
        if not swapped:
            shutil.rmtree(tmp, ignore_errors=True)
    # Sweep residue from PRIOR interrupted saves only after this save fully
    # landed: a complete `.old-*`/`.tmp-*` sibling is load_group's crash
    # fallback and must never be deleted before a newer complete copy exists.
    # rtap: allow[replay-determinism] — every match is deleted; order-free
    for stale in path.parent.glob(f".{path.name}.tmp-*"):
        if stale != tmp:
            shutil.rmtree(stale, ignore_errors=True)
    # rtap: allow[replay-determinism] — every match is deleted; order-free
    for stale in path.parent.glob(f".{path.name}.old-*"):
        shutil.rmtree(stale, ignore_errors=True)
    obs.counter("rtap_obs_checkpoint_saves_total",
                "atomic per-group checkpoint saves that fully landed").inc()
    obs.histogram("rtap_obs_checkpoint_save_seconds",
                  "wall seconds per group save (state fetch + orbax write + "
                  "swap)").observe(time.perf_counter() - t_save)
    n = _tree_bytes(tree)
    _count_bytes(n, "save")
    return n


def _recover_residue(path: Path) -> Path:
    """If `path` is missing but a complete residue sibling from an
    interrupted save exists (meta.json present), rename it into place and
    return `path`; otherwise return `path` unchanged (load will fail with
    the underlying error)."""
    if (path / "meta.json").exists():
        return path
    # sorted so an mtime TIE between two residue dirs resolves to the
    # same winner on every host (max keeps the first of equal keys)
    candidates = sorted(
        p
        for pattern in (f".{path.name}.old-*", f".{path.name}.tmp-*")
        for p in path.parent.glob(pattern)
        if (p / "meta.json").exists()
    )
    if candidates:
        import logging

        best = max(candidates, key=lambda p: (p / "meta.json").stat().st_mtime)
        logging.getLogger(__name__).warning(
            "checkpoint %s missing; recovering interrupted-save residue %s", path, best
        )
        if not path.exists():
            best.rename(path)
    return path


def load_group(path: str | Path, mesh=None, sparsify: bool = False,
               trace=None) -> StreamGroup:
    """Rebuild a StreamGroup from `path`; scoring continues bit-identically.

    One `rtap.checkpoint.load` span (`group`, `bytes`; into `trace`'s ring
    too) covers the read, the re-layout and the put. The group is built
    WITHOUT a state of its own (`make_state=False`): beside whatever the
    caller already holds, a load puts one group's state on the device, never
    two.

    A group checkpointed while sharded over a mesh records that fact; pass
    `mesh` to re-shard on resume. Resuming a sharded checkpoint without a mesh
    downgrades to single-device and logs a warning (the state itself is
    topology-independent — only placement changes).

    `sparsify` migrates a DENSE-layout SP pool checkpoint into the sparse
    member-index layout on the way in (models/migrate.py): the resumed
    group's config gains ``sparse_pool=True`` with the migration's exact
    pool width pinned via ``pool_members``, and scoring continues
    BIT-IDENTICALLY to the dense run (the re-layout is lossless — see
    docs/MIGRATION.md). Already-sparse checkpoints are untouched.
    """
    sp = span("rtap.checkpoint.load", trace).begin()
    try:
        grp, n = _load_group(path, mesh, sparsify)
    except BaseException:
        sp.end(record=False)
        raise
    sp.end(group=grp.stream_ids[0], bytes=n)
    return grp


def _load_group(path, mesh, sparsify) -> tuple[StreamGroup, int]:
    """load_group's body -> (the group, the bytes of the state tree read)."""
    import jax
    import orbax.checkpoint as ocp

    path = _recover_residue(Path(path).absolute())
    meta = json.loads((path / "meta.json").read_text())
    cfg = ModelConfig.from_dict(meta["config"])
    if meta.get("sharded") and mesh is None:
        import logging

        logging.getLogger(__name__).warning(
            "checkpoint %s was saved sharded over a mesh; resuming single-device "
            "(pass mesh= to load_group to restore the sharded topology)", path
        )
    with ocp.PyTreeCheckpointer() as ckptr:
        tree = ckptr.restore(path / "state")
    if sparsify and not cfg.sp.sparse_pool:
        from rtap_tpu.models.migrate import (
            sparse_pool_width, sparsify_config, sparsify_sp_state)

        n_slots = len(meta["stream_ids"])
        if meta["backend"] == "tpu":
            # batched tree [G, C, n_in]: one migration call, one shared P
            model = {k: np.asarray(v) for k, v in tree["model"].items()}
            P = sparse_pool_width(model["potential"])
            tree["model"] = sparsify_sp_state(model, P)
        else:
            # per-stream dicts share the group's config, so the pool width
            # is the max over all streams (narrower columns pad with -1)
            P = max(
                sparse_pool_width(np.asarray(tree["model"][f"s{g}"]["potential"]))
                for g in range(n_slots))
            for g in range(n_slots):
                tree["model"][f"s{g}"] = sparsify_sp_state(
                    {k: np.asarray(v) for k, v in tree["model"][f"s{g}"].items()}, P)
        cfg = sparsify_config(cfg, P)
    grp = StreamGroup(
        cfg, meta["stream_ids"], backend=meta["backend"], threshold=meta["threshold"],
        mesh=mesh, debounce=int(meta.get("debounce", 1)),
        predict=int(meta.get("predict", 0)), make_state=False,
    )
    if grp.backend == "tpu":
        # fwd_*: a forward index an older build may have stored
        from rtap_tpu.ops.resident import host_resident

        model = {k: v for k, v in tree["model"].items() if not k.startswith("fwd_")}
        # the files hold the public layout; the device the kernel's form,
        # taken on the host before the put (never two pools on the chip)
        model = host_resident(model, cfg.tm, grp)
        if mesh is not None:
            from rtap_tpu.parallel.sharding import shard_state

            grp.resident = shard_state(model, mesh)
        else:
            grp.resident = jax.device_put(model)
    else:
        for g in range(grp.G):
            saved = tree["model"][f"s{g}"]
            for k in grp._states[g]:
                grp._states[g][k] = np.asarray(saved[k])
    grp.likelihood.load_state_dict(tree["likelihood"])
    if "alert_run" in tree:  # pre-debounce checkpoints lack it (zeros then)
        grp._alert_run = np.asarray(tree["alert_run"]).astype(np.int64)
    grp.ticks = int(meta["ticks"])
    # the alert-delivery cursor rides along for resume-time suppression
    # (None for pre-durability checkpoints: the scan falls back to 0)
    grp.resume_alerts_offset = (
        int(meta["alerts_offset"]) if "alerts_offset" in meta else None)
    grp.resume_journal_tick = (
        int(meta["journal_tick"]) if "journal_tick" in meta else None)
    grp.alert_epoch = int(meta.get("alert_epoch", 0))
    # the predictive tracker's latches and open windows, where the saving
    # run had a tracker (None: the resuming tracker starts its rule afresh)
    grp.resume_predict_state = (
        {"latches": {k: np.asarray(v)
                     for k, v in tree["predict_latches"].items()},
         "blast": meta.get("predict_blast")}
        if "predict_latches" in tree else None)
    # n_live is now derived from stream_ids (pad-prefix count) — the meta
    # field stays written for inspection/back-compat but is not load-bearing
    get_registry().counter(
        "rtap_obs_checkpoint_loads_total",
        "group checkpoints restored (service/replay resume)").inc()
    n = _tree_bytes(tree)
    _count_bytes(n, "load")
    return grp, n


def peek_resume_ticks(checkpoint_dir: str | Path) -> int:
    """Max recorded tick cursor across a dir's group checkpoints, read
    from meta.json alone (no state load) — the serve CLI's resume-base
    probe when ``--journal-dir`` treats ``--ticks`` as a total budget
    across restarts. 0 for a missing/empty/unreadable dir."""
    best = 0
    root = Path(checkpoint_dir)
    if not root.is_dir():
        return 0
    for d in sorted(root.iterdir()):
        if not d.name.startswith("group") or not d.is_dir():
            continue
        try:
            best = max(best,
                       int(json.loads((d / "meta.json").read_text())["ticks"]))
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return best


def peek_resume_predict(checkpoint_dir: str | Path) -> int | None:
    """The predictive horizon the dir's group checkpoints were saved with
    (meta.json alone, no state load; 0 = warmed without the predictor):
    the serve CLI's usage check for ``--predict`` with ``--checkpoint-dir``.
    None for a missing/empty/unreadable dir or a torn set that disagrees
    (the resume itself then says which group)."""
    seen = set()
    root = Path(checkpoint_dir)
    if not root.is_dir():
        return None
    for d in sorted(root.iterdir()):
        if not d.name.startswith("group") or not d.is_dir():
            continue
        try:
            seen.add(int(json.loads(
                (d / "meta.json").read_text()).get("predict", 0)))
        except (OSError, ValueError, TypeError):
            continue
    return seen.pop() if len(seen) == 1 else None


def horizon_mismatch(ck_path, saved: int, requested: int) -> str:
    """What a resume across a horizon change is told: both horizons, the
    checkpoint, the remedy. The predictor's ring lives INSIDE the state
    tree, sized by the horizon — there is no blend of two."""
    def said(k: int) -> str:
        return f"--predict --predict-horizon {k}" if k else "no --predict"

    return (
        f"checkpoint {ck_path} was saved with predictive horizon {saved} "
        f"({said(saved)}); this run asks for {requested} "
        f"({said(requested)}). The predictor's ring is part of the saved "
        f"state, sized by the horizon: serve it with {said(saved)}, or "
        f"re-warm the fleet into a fresh --checkpoint-dir with "
        f"{said(requested)} (python -m rtap_tpu replay ... "
        f"--checkpoint-dir, docs/PREDICT.md)")


def validate_resume(resumed: StreamGroup, ck_path, grp: StreamGroup,
                    allow_claimed_extras: bool = False) -> None:
    """Shared resume-safety gate for replay_streams and live_loop: a resumed
    group silently carries its checkpoint's model config and alerting
    semantics, so the checkpoint must MATCH what this run would have built —
    mixing them would blend two semantics in one result. Mismatches are
    errors, not surprises. Add new load-bearing fields here, once, so both
    entry points stay in lockstep.

    `allow_claimed_extras` (serve --auto-register): slots this run built as
    PADS may hold real streams in the checkpoint — they were lazily claimed
    in the prior run and rightfully resume live (the caller reconciles
    registry routing). Pad names may differ (released slots get unique
    names). Every REQUESTED stream must still match its slot exactly."""
    from rtap_tpu.service.registry import PAD_PREFIX

    if len(resumed.stream_ids) != len(grp.stream_ids):
        raise ValueError(
            f"checkpoint {ck_path} has {len(resumed.stream_ids)} slots but "
            f"this group was built with {len(grp.stream_ids)}; refusing to "
            "resume")
    for slot, (ck_id, want_id) in enumerate(
            zip(resumed.stream_ids, grp.stream_ids)):
        if ck_id == want_id:
            continue
        ck_pad = ck_id.startswith(PAD_PREFIX)
        want_pad = want_id.startswith(PAD_PREFIX)
        if ck_pad and want_pad:
            continue  # pad naming is not load-bearing (released slots)
        if allow_claimed_extras and want_pad and not ck_pad:
            continue  # a previously auto-registered stream resumes live
        raise ValueError(
            f"checkpoint {ck_path} holds {ck_id!r} at slot {slot} but this "
            f"group expects {want_id!r}; refusing to resume"
            + ("" if allow_claimed_extras else
               " (lazily claimed extras resume under serve"
               " --auto-register, or frozen via serve --freeze)"))
    # the predictor leaves live INSIDE the state tree: resuming across a
    # horizon change would need a structural migration, not a silent blend
    saved_k, want_k = getattr(resumed, "predict", 0), getattr(grp, "predict", 0)
    if saved_k != want_k:
        raise ValueError(horizon_mismatch(ck_path, saved_k, want_k))
    mismatches = [
        f"{name}: checkpoint={a!r} vs requested={b!r}"
        for name, a, b in (
            ("config", resumed.cfg, grp.cfg),
            ("threshold", resumed.threshold, grp.threshold),
            ("debounce", resumed.debounce, grp.debounce),
        )
        if a != b
    ]
    if mismatches:
        raise ValueError(
            f"checkpoint {ck_path} disagrees with this run's parameters "
            f"({'; '.join(mismatches)}); rerun with the checkpointed "
            "settings or use a fresh checkpoint dir")
