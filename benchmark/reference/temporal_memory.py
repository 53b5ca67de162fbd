"""Temporal Memory — numpy oracle over dense bounded segment pools.

Semantics per SURVEY.md C4/C5 / §3.2 (NuPIC `temporal_memory.py` +
`Connections.cpp`): per-cell distal segments; correctly-predicted cells
activate and learn; unpredicted active columns burst, pick a winner cell
(best matching segment, else fewest segments) and learn/grow; matching
segments in columns that failed to activate are punished; synapses die at
permanence <= 0; full cell pools evict the least-recently-used segment.

NuPIC's pointer-graph Connections store is replaced by fixed-capacity dense
pools [C, K, S, M] (SURVEY.md §7 design stance) — empty synapse slots hold
presyn = -1, free segment slots hold seg_last = -1. Deliberate deviations,
shared with the TPU kernel so backends agree exactly:
- all tie-breaks (winner cell, best segment, slot choice) are lowest-index,
  not RNG-driven;
- growth candidates are taken in ascending prev-winner cell order rather
  than random sample;
- when a full segment needs room to grow, its weakest synapses are evicted
  (NuPIC's destroyMinPermanenceSynapses, minus its random tie-break).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.config import TMConfig
from benchmark.reference.perm import tm_domain


def _grow_synapses(
    state: dict, c: int, k: int, s: int, candidates: np.ndarray, n: int, cfg: TMConfig
) -> None:
    """Add up to n synapses on segment (c,k,s) to candidate cells (ascending
    id) not already presynaptic; evict weakest synapses if slots run short."""
    if n <= 0 or len(candidates) == 0:
        return
    presyn = state["presyn"][c, k, s]
    perm = state["syn_perm"][c, k, s]
    existing = presyn[presyn >= 0]
    new_ids = candidates[~np.isin(candidates, existing)][:n]
    if len(new_ids) == 0:
        return
    free = np.nonzero(presyn < 0)[0]
    short = len(new_ids) - len(free)
    if short > 0:
        # evict weakest existing synapses to make room (bounded-pool rule)
        occupied = np.nonzero(presyn >= 0)[0]
        order = occupied[np.argsort(perm[occupied], kind="stable")]
        evict = order[:short]
        presyn[evict] = -1
        perm[evict] = 0.0
        free = np.nonzero(presyn < 0)[0]
    slots = free[: len(new_ids)]
    presyn[slots] = new_ids[: len(slots)]
    perm[slots] = tm_domain(cfg).rate(cfg.initial_permanence)


def _reinforce_and_grow(
    state: dict,
    c: int,
    k: int,
    s: int,
    prev_active_flat: np.ndarray,
    prev_winner_ids: np.ndarray,
    cfg: TMConfig,
    it: int,
) -> None:
    """Adapt one learning segment: +inc on synapses to previously-active
    cells, -dec on the rest, then grow toward prev winner cells until the
    segment has new_synapse_count active-potential synapses."""
    presyn = state["presyn"][c, k, s]
    exists = presyn >= 0
    act = exists & prev_active_flat[np.clip(presyn, 0, None)]
    # Domain compute dtype: f32 constants in the f32 domain (a python float *
    # bool-array promotes to f64 and the f64-compute-then-f32-store
    # double-rounds, diverging 1 ulp from the device's pure-f32 chain —
    # observed); int32 in quantized domains (no wrap before the clip).
    dom = tm_domain(cfg)
    state["syn_perm"][c, k, s] = np.clip(
        state["syn_perm"][c, k, s].astype(dom.compute_dtype)
        + dom.rate(cfg.permanence_increment) * act
        - dom.rate(cfg.permanence_decrement) * (exists & ~act),
        dom.zero,
        dom.one,
    ).astype(dom.dtype)
    state["seg_last"][c, k, s] = it
    n_grow = cfg.new_synapse_count - int(state["seg_pot"][c, k, s])
    _grow_synapses(state, c, k, s, prev_winner_ids, n_grow, cfg)


def _allocate_segment(state: dict, c: int, k: int, it: int) -> int:
    """Lowest free slot in cell (c,k)'s pool, else evict the LRU segment."""
    seg_last = state["seg_last"][c, k]
    free = np.nonzero(seg_last < 0)[0]
    if len(free):
        s = int(free[0])
    else:
        s = int(np.argmin(seg_last))
        state["presyn"][c, k, s] = -1
        state["syn_perm"][c, k, s] = 0.0
        state["active_seg"][c, k, s] = False
        state["matching_seg"][c, k, s] = False
        state["seg_pot"][c, k, s] = 0
    state["seg_last"][c, k, s] = it
    return s


class TMOracle:
    """Stateful wrapper: compute(active_cols, learn) -> raw anomaly score."""

    def __init__(self, state: dict, cfg: TMConfig):
        self.state = state
        self.cfg = cfg

    def compute(self, active_cols: np.ndarray, learn: bool = True) -> float:
        state, cfg = self.state, self.cfg
        C, K, S, M = state["presyn"].shape
        prev_predictive = state["active_seg"].any(-1)  # [C, K] cells predicted for t
        prev_pred_cols = prev_predictive.any(-1)  # [C]

        n_active = int(active_cols.sum())
        # f32 arithmetic: the device step emits raw as f32, and the score is
        # part of the cross-backend parity contract — round the same way here.
        raw_anomaly = (
            float(np.float32(1.0) - np.float32((active_cols & prev_pred_cols).sum()) / np.float32(n_active))
            if n_active
            else 0.0
        )

        active_cells = np.zeros((C, K), bool)
        winner_cells = np.zeros((C, K), bool)
        prev_active_flat = state["prev_active"].reshape(-1)
        prev_winner_ids = np.nonzero(state["prev_winner"].reshape(-1))[0]
        it = int(state["tm_iter"]) + 1

        for c in np.nonzero(active_cols)[0]:
            pred = np.nonzero(prev_predictive[c])[0]
            if len(pred):
                # correctly predicted column: predicted cells activate + learn
                active_cells[c, pred] = True
                winner_cells[c, pred] = True
                if learn:
                    for k in pred:
                        for s in np.nonzero(state["active_seg"][c, k])[0]:
                            _reinforce_and_grow(
                                state, c, int(k), int(s), prev_active_flat, prev_winner_ids, cfg, it
                            )
            else:
                # burst
                active_cells[c, :] = True
                matching = state["matching_seg"][c]  # [K, S]
                if matching.any():
                    pot = np.where(matching, state["seg_pot"][c], -1)
                    k, s = np.unravel_index(int(np.argmax(pot)), pot.shape)
                    winner_cells[c, k] = True
                    if learn:
                        _reinforce_and_grow(
                            state, c, int(k), int(s), prev_active_flat, prev_winner_ids, cfg, it
                        )
                else:
                    seg_counts = (state["seg_last"][c] >= 0).sum(-1)  # [K]
                    k = int(np.argmin(seg_counts))
                    winner_cells[c, k] = True
                    if learn and len(prev_winner_ids):
                        s = _allocate_segment(state, c, k, it)
                        _grow_synapses(
                            state, c, k, s, prev_winner_ids, cfg.new_synapse_count, cfg
                        )

        if learn and cfg.predicted_segment_decrement > 0.0:
            # punish matching segments in columns that did not activate
            seg_mask = state["matching_seg"] & ~active_cols[:, None, None]
            idx = np.nonzero(seg_mask)
            if len(idx[0]):
                dom = tm_domain(cfg)
                presyn = state["presyn"][idx]
                act = (presyn >= 0) & prev_active_flat[np.clip(presyn, 0, None)]
                state["syn_perm"][idx] = np.maximum(
                    state["syn_perm"][idx].astype(dom.compute_dtype)
                    - dom.rate(cfg.predicted_segment_decrement) * act,
                    dom.zero,
                ).astype(dom.dtype)

        if learn:
            # synapse death at permanence <= 0, then segment death at 0 synapses
            dead = (state["presyn"] >= 0) & (state["syn_perm"] <= 0.0)
            state["presyn"][dead] = -1
            nsyn = (state["presyn"] >= 0).sum(-1)
            empty = (state["seg_last"] >= 0) & (nsyn == 0)
            state["seg_last"][empty] = -1

        # dendrite activity for the next step, over existing segments only
        exist_idx = np.nonzero(state["seg_last"] >= 0)
        active_seg = np.zeros((C, K, S), bool)
        matching_seg = np.zeros((C, K, S), bool)
        seg_pot = np.zeros((C, K, S), np.int16)
        if len(exist_idx[0]):
            presyn = state["presyn"][exist_idx]  # [Nseg, M]
            syn_act = (presyn >= 0) & active_cells.reshape(-1)[np.clip(presyn, 0, None)]
            connected = tm_domain(cfg).threshold(cfg.connected_permanence)
            conn_count = (syn_act & (state["syn_perm"][exist_idx] >= connected)).sum(-1)
            pot_count = syn_act.sum(-1)
            active_seg[exist_idx] = conn_count >= cfg.activation_threshold
            matching_seg[exist_idx] = pot_count >= cfg.min_threshold
            seg_pot[exist_idx] = pot_count
            if learn:
                # LRU stamp only while learning (NuPIC records lastUsedIteration
                # under learn; inference must not perturb eviction order)
                state["seg_last"][active_seg] = it

        state["active_seg"] = active_seg
        state["matching_seg"] = matching_seg
        state["seg_pot"] = seg_pot
        state["prev_active"] = active_cells
        state["prev_winner"] = winner_cells
        state["tm_iter"] = np.int32(it)
        return raw_anomaly
