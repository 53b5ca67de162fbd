"""The plain reference of the predictive horizon: the reducer over the
reference model's own state, the paging rule over a node's divergence
trajectory, the fusion of precursors into one page a service, and the
model-health reducer's leaf from a group's state (per-node counts, and their
means over a tick's live nodes).

The benchmark's own statement of the semantics of rtap_tpu/ops/predict_tpu.py
(twin: rtap_tpu/models/oracle/predict.py:predict_update_host),
rtap_tpu/predict/horizon.py:PredictTracker and rtap_tpu/predict/blast.py:
BlastFuser, in numpy, importing nothing of the program. Where it departs
from models/oracle/predict.py:

- one node at a time on the reference's public-layout state (`active_seg`
  [C, K, S], `prev_active` [C, K]), where the twin takes a group's stacked
  [G, ...] leaves;
- the tick is the index of the row in the node's feed, counted from the
  making of its state (the twin reads the group's lockstep `tm_iter` - 1:
  the same number for a node that has been in its group from tick 0);
- `tick0` is an argument of the reducer, where the twin reads the
  `pred_tick0` leaf (0 for every node of a fleet armed from tick 0);
- the paging rule and the fusion are here too, as pure functions of a
  trajectory and of a list of precursors; the program keeps them in the
  host trackers, which also emit, count and suppress — none of that is
  semantics a run can be held to and none of it is copied;
- a precursor's `blast_radius` is computed from the declared topology and
  the nodes observed, as sets; the program sorts them into a list.

- the health leaf's means are taken in float64 from whole counts, where the
  program sums float32 fractions on the device (a few ulp of a sum of 1,024
  terms: the configuration's `health_tolerance`); the bins are exact.

All of the reducer's arithmetic is float32 with the power-of-two step 1/8,
as the program's: on one backend the two agree bit for bit; the TPU's f32
divide rounds 1 ulp off numpy's (PERF.md §2)."""

from __future__ import annotations

import numpy as np

from benchmark.reference.config import ModelConfig
from benchmark.reference.model import ReferenceStream
from benchmark.reference.perm import tm_domain

#: the divergence EWMA's step, a power of two
ALPHA = np.float32(0.125)


class HorizonReducer:
    """One node's predictor state: a `k`-deep ring of predicted-active
    column sets, the miss EWMA (NaN until the first scored tick) and the
    tick the ring was made at."""

    def __init__(self, horizon: int, columns: int, tick0: int = 0):
        self.k, self.C, self.tick0 = int(horizon), int(columns), int(tick0)
        self.ring = np.zeros((self.k, self.C), bool)
        self.ewma = np.float32(np.nan)

    def update(self, t: int, state: dict, live: bool) -> tuple:
        """Fold tick `t` from the node's POST-step `state` -> (overlap,
        miss_ewma, pred_col_frac, scored). Slot ``t % k`` is read (the set
        captured at ``t - k``), then overwritten with this tick's."""
        active = np.asarray(state["prev_active"]).any(-1)           # [C]
        predicted = np.asarray(state["active_seg"]).any((-1, -2))   # [C]
        slot = t % self.k
        old = self.ring[slot]
        n_active = np.float32(active.sum())
        overlap = np.float32((old & active).sum()) \
            / np.maximum(n_active, np.float32(1.0))
        miss = np.float32(1.0) - overlap
        scored = bool(live) and t >= self.tick0 + self.k
        if scored:
            self.ewma = miss if np.isnan(self.ewma) else np.float32(
                self.ewma + ALPHA * np.float32(miss - self.ewma))
        self.ring[slot] = predicted
        return (overlap if scored else np.float32(np.nan), self.ewma,
                np.float32(predicted.sum()) / np.float32(self.C), scored)


def stream_health(state: dict, connected: int) -> dict:
    """The per-node counts the model-health reducer turns into its group
    means (`connected`: the connected permanence in the state's quanta)."""
    used = np.asarray(state["presyn"]) >= 0
    return {
        "seg_used": int((np.asarray(state["seg_last"]) >= 0).sum()),
        "syn_used": int(used.sum()),
        "syn_connected": int(((np.asarray(state["syn_perm"]) >= connected)
                              & used).sum()),
        "active_columns": int(np.asarray(state["prev_active"]).any(-1).sum()),
        "predicted_cells": int(np.asarray(state["active_seg"]).any(-1).sum()),
    }


#: bins of the health leaf's two sketches (the program's OCC_BINS, PERM_BINS)
HEALTH_BINS = 8


def health_counts(rows: dict, connected: int, one: int) -> dict:
    """`stream_health`'s counts for a block of nodes at once ([n, ...]
    public-layout leaves -> [n] integer arrays), and each node's permanence
    sketch: its non-empty synapses counted into `HEALTH_BINS` equal bins of
    the permanence domain [0, `one`] (`perm_bins` [n, HEALTH_BINS])."""
    presyn = np.asarray(rows["presyn"])
    n = presyn.shape[0]
    used = (presyn >= 0).reshape(n, -1)
    perm = np.asarray(rows["syn_perm"]).reshape(n, -1)
    # the bin of a permanence, in the float32 the program bins it in
    pbin = np.clip((perm.astype(np.float32) / np.float32(one)
                    * np.float32(HEALTH_BINS)).astype(np.int32),
                   0, HEALTH_BINS - 1)
    return {
        "seg_used": (np.asarray(rows["seg_last"]) >= 0).reshape(n, -1).sum(1),
        "syn_used": used.sum(1),
        "syn_connected": ((perm >= connected) & used).sum(1),
        "active_columns": np.asarray(rows["prev_active"]).any(-1).sum(-1),
        "predicted_cells": np.asarray(rows["active_seg"]).any(-1)
        .reshape(n, -1).sum(1),
        "perm_bins": np.stack([((pbin == b) & used).sum(1)
                               for b in range(HEALTH_BINS)], axis=1),
    }


def health_means(counts: dict, live, model: dict) -> dict:
    """The model-health leaf of one group-tick, as far as the state alone
    decides it: every node's counts as fractions of the configuration's
    capacities, averaged over the `live` nodes of the tick (those that sent
    any finite field), in float64 from whole numbers; `occ_hist` counts the
    live nodes into `HEALTH_BINS` bins of their used-segment fraction. The
    leaf's `hit_num`, `hit_den` and `score_hist` need every node's raw
    score of the tick and are not here."""
    cfg = ModelConfig.from_dict(model)
    C, K = cfg.sp.columns, cfg.tm.cells_per_column
    S, M = cfg.tm.max_segments_per_cell, cfg.tm.max_synapses_per_segment
    live = np.asarray(live, bool)
    n_live = max(int(live.sum()), 1)

    def mean(x):
        return float(np.asarray(x, np.float64)[live].sum() / n_live)

    syn_used = np.asarray(counts["syn_used"], np.float64)
    denom = np.maximum(syn_used, 1.0)
    # a node's occupancy bin, in the float32 the program bins it in
    occ = np.asarray(counts["seg_used"]).astype(np.float32) \
        / np.float32(C * K * S)
    occ_bin = np.clip((occ * np.float32(HEALTH_BINS)).astype(np.int32),
                      0, HEALTH_BINS - 1)
    return {
        "occ_hist": np.bincount(occ_bin[live], minlength=HEALTH_BINS),
        "seg_occ_frac": mean(counts["seg_used"] / (C * K * S)),
        "syn_frac": mean(syn_used / (C * K * S * M)),
        "perm_hist": np.array([mean(counts["perm_bins"][:, b] / denom)
                               for b in range(HEALTH_BINS)]),
        "perm_conn_frac": mean(counts["syn_connected"] / denom),
        "act_col_frac": mean(counts["active_columns"] / C),
        "pred_cell_frac": mean(counts["predicted_cells"] / (C * K)),
    }


def connected_quanta(model: dict) -> int:
    """The connected permanence of a configuration's `model`, in the quanta
    its TM's permanences are held in."""
    tm = ModelConfig.from_dict(model).tm
    return tm_domain(tm).threshold(tm.connected_permanence)


def one_quanta(model: dict) -> int:
    """Permanence 1.0 in the quanta of a configuration's TM."""
    return tm_domain(ModelConfig.from_dict(model).tm).one


def follow(model: dict, seed: int, ts, values, horizon: int) -> dict:
    """One node from the making of its state through every row of its feed
    -> the predict leaves of every tick ([T] each), the ring and the EWMA
    after the last, and `stream_health` of the final state."""
    cfg = ModelConfig.from_dict(model)
    ref = ReferenceStream(cfg, seed)
    red = HorizonReducer(horizon, cfg.sp.columns)
    T = len(ts)
    out = {"overlap": np.empty(T, np.float32),
           "miss_ewma": np.empty(T, np.float32),
           "pred_col_frac": np.empty(T, np.float32),
           "scored": np.empty(T, bool)}
    for t in range(T):
        row = np.atleast_1d(np.asarray(values[t], np.float32))
        ref.run(int(ts[t]), row)
        leaf = red.update(t, ref.state, bool(np.isfinite(row).any()))
        for key, v in zip(out, leaf):
            out[key][t] = v
    return {**out, "pred_ring": red.ring.copy(),
            "pred_miss_ewma": np.float32(red.ewma),
            "health": stream_health(ref.state, connected_quanta(model))}


def precursor_ticks(scored, miss_ewma, threshold: float, min_ticks: int,
                    warmup_ticks: int, rearm_frac: float) -> list[int]:
    """The paging rule over one node's trajectory, from its first tick ->
    the ticks at which a `precursor` fires. A node may alarm once it has
    `warmup_ticks` scored samples; hot = scored with the EWMA at or above
    `threshold`; `min_ticks` consecutive hot scored ticks fire one
    precursor (a scored cool tick resets the run, an unscored tick holds
    it); the node re-arms on a scored tick with the EWMA under
    `rearm_frac * threshold`."""
    run = samples = 0
    alarmed = False
    fired = []
    for t, (s, e) in enumerate(zip(np.asarray(scored, bool),
                                   np.asarray(miss_ewma, np.float64))):
        finite = bool(np.isfinite(e))
        hot = bool(s) and finite and e >= threshold
        run = run + 1 if hot else (0 if s else run)
        samples += int(s)
        was = alarmed
        if not alarmed and run >= min_ticks and samples >= warmup_ticks:
            alarmed = True
            fired.append(t)
        if was and s and finite and e < rearm_frac * threshold:
            alarmed, run = False, 0
    return fired


def fuse(precursors, cluster_of, declared: dict, window_ticks: int
         ) -> list[dict]:
    """Precursors -> predicted incidents. `precursors`: (node, tick) in the
    order the tracker met them; `cluster_of`: node -> its cluster's key;
    `declared`: cluster -> the nodes the topology declares in it. The first
    precursor in a cluster's window emits one incident naming that node and
    the cluster's whole radius (declared nodes and nodes observed); later
    ones attach; the window closes once `window_ticks` ticks pass with no
    new member."""
    last: dict[str, int] = {}
    seen: dict[str, set] = {c: set(nodes) for c, nodes in declared.items()}
    incidents = []
    for node, tick in precursors:
        cluster = cluster_of(node)
        seen.setdefault(cluster, set()).add(node)
        if cluster in last and tick - last[cluster] > window_ticks:
            del last[cluster]
        if cluster in last:
            last[cluster] = max(last[cluster], tick)
            continue
        last[cluster] = tick
        incidents.append({"cluster": cluster, "tick": int(tick),
                          "first_node": node,
                          "blast_radius": frozenset(seen[cluster])})
    return incidents
