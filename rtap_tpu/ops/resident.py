"""Where a model's state lives between programs, and the edges that convert it.

A `StreamGroup` (service/registry.py) or a `TpuStepRunner` (ops/step.py)
holds its state on the device in the form the kernel runs on
(`tm_tpu.resident_form`: the six leaves of `tm_tpu._KERNEL_KEYS` as
[C, K*S*M] pools and [C, K*S] segment tensors at narrow rows, [C, M, K*S]
pools at wide ones — a function of `TMConfig` alone), and the entry points of
ops/step.py hand a tree back in the form it arrived in. So a program's
parameters and results are its scan's carry, and no pool changes layout at a
program's boundary (docs/KERNELS.md, "Where the state lives between
programs"; PERF.md §6, PR 44).

The public [C, K, S, M] layout exists only at the edges, off every tick's
path. Each conversion there runs under ONE span, `rtap.state.relayout`
(arguments `leaves`, `bytes`), and counts on its owner (`relayouts`):

- a state made: `host_resident` of `init_state`'s tree, numpy views, before
  the on-chip broadcast;
- a slot claimed: the fresh row re-laid on the host (`host_resident`), one
  row's worth, before `step.set_state_row` writes it;
- a checkpoint written or read: the files keep the public layout, converted
  on the host after the fetch / before the put;
- a row read for inspection: `owner.state[k][slot]` slices the resident leaf
  on its stream axis FIRST and re-lays that row on the host — never the
  leaf (a public copy of one NAB-width pool is 2.28 GB);
- a public tree assigned (`owner.state = tree`): converted once, there.

`PublicState` is what `owner.state` reads as: a mapping with the public
shapes, so every reader written against [C, K, S, M] keeps its meaning. An
owner is anything with `resident` (the plain dict of device leaves), `cfg`
(its ModelConfig) and `relayouts` (an int).
"""

from __future__ import annotations

from collections.abc import MutableMapping

import jax
import numpy as np

from rtap_tpu.config import TMConfig
from rtap_tpu.obs.trace import span
from rtap_tpu.ops.tm_tpu import _KERNEL_KEYS, public_leaf, resident_leaf, wide_rows

__all__ = ["PublicLeaf", "PublicState", "host_public", "host_resident", "relaid",
           "resident_tree"]


class relaid:
    """One conversion between the layouts outside a program: the span
    `rtap.state.relayout` around it and one count on `owner.relayouts`
    (where there is an owner). A context manager."""

    __slots__ = ("_span", "_owner")

    def __init__(self, owner, leaves):
        sizes = [int(np.prod(np.shape(x), dtype=np.int64)) * np.dtype(x.dtype).itemsize
                 for x in leaves]
        self._owner = owner
        self._span = span("rtap.state.relayout", leaves=len(sizes), bytes=sum(sizes))

    def __enter__(self):
        self._span.begin()
        return self

    def __exit__(self, *exc) -> None:
        self._span.end()
        if self._owner is not None:
            self._owner.relayouts += 1


def resident_tree(tree, cfg: TMConfig, owner=None) -> dict:
    """A state tree as anyone may hand it over -> the plain dict of leaves
    an owner holds. A `PublicLeaf` gives its resident array back untouched
    (the tree was read from an owner: `{**grp.state, k: v}`); a raw leaf in
    the public layout is converted — as a numpy view if it is the host's,
    on the device if it is there; one in the kernel's form passes."""
    out = {k: (v.resident if isinstance(v, PublicLeaf) else v)
           for k, v in tree.items()}
    lead = np.ndim(out["prev_active"]) - 2  # axes before the column axis
    public = [k for k, nd in _KERNEL_KEYS.items() if np.ndim(out[k]) == lead + 1 + nd]
    if public:
        with relaid(owner, [out[k] for k in public]):
            for k in public:
                out[k] = resident_leaf(k, out[k], cfg)
    return out


def host_resident(tree: dict, cfg: TMConfig, owner=None) -> dict:
    """A HOST tree in the public layout (`init_state`'s, a checkpoint's) ->
    the resident form as numpy views: nothing is copied until the leaves
    are put on the device."""
    return resident_tree({k: np.asarray(v) for k, v in tree.items()}, cfg, owner)


def host_public(tree: dict, cfg: TMConfig, owner=None) -> dict:
    """`host_resident`'s inverse: a fetched resident tree -> the public
    layout, every leaf contiguous — what a checkpoint writes."""
    with relaid(owner, [tree[k] for k in _KERNEL_KEYS]):
        return {k: (np.ascontiguousarray(public_leaf(k, np.asarray(v), cfg))
                    if k in _KERNEL_KEYS else np.asarray(v))
                for k, v in tree.items()}


class PublicLeaf:
    """One resident leaf of `_KERNEL_KEYS`, read as its public self.

    Lazy: `shape` / `dtype` / `ndim` cost nothing. `leaf[i]` (an index on
    the leading axis — a group's stream axis) slices the resident array
    there first, fetches those rows and re-lays them on the host, so one
    stream's row of a pool never costs a second pool on the device.
    `np.asarray(leaf)` fetches the resident leaf and re-lays it on the host.
    That is all a leaf does: arithmetic is done on what those two give."""

    __slots__ = ("_owner", "_key", "resident")

    def __init__(self, owner, key: str, resident):
        self._owner, self._key, self.resident = owner, key, resident

    def _public(self, x):
        """`x` (the resident leaf, or rows of it) -> its public layout."""
        with relaid(self._owner, (x,)):
            return public_leaf(self._key, x, self._owner.cfg.tm)

    @property
    def _streams(self) -> bool:
        """Does the leaf carry a leading stream axis (a group's does)?"""
        tm = self._owner.cfg.tm
        own = 3 if _KERNEL_KEYS[self._key] == 3 and wide_rows(tm) else 2
        return self.resident.ndim > own

    @property
    def shape(self) -> tuple:
        tm = self._owner.cfg.tm
        tail = (tm.cells_per_column, tm.max_segments_per_cell,
                tm.max_synapses_per_segment)[: _KERNEL_KEYS[self._key]]
        return (*self.resident.shape[: 1 + self._streams], *tail)

    @property
    def dtype(self):
        return self.resident.dtype

    @property
    def ndim(self) -> int:
        return 1 + self._streams + _KERNEL_KEYS[self._key]

    def __getitem__(self, idx):
        first, rest = (idx[0], idx[1:]) if isinstance(idx, tuple) else (idx, ())
        if not self._streams or first is Ellipsis or first is None:
            return np.asarray(self)[idx]  # no stream axis to slice first
        rows = self._public(np.asarray(self.resident[first]))
        if not rest:
            return rows
        return rows[rest] if rows.ndim < self.ndim else rows[(slice(None), *rest)]

    def __array__(self, dtype=None, copy=None):
        out = np.ascontiguousarray(self._public(np.asarray(self.resident)))
        return out if dtype is None else out.astype(dtype, copy=False)

    def __repr__(self) -> str:
        return f"PublicLeaf({self._key!r}, shape={self.shape}, dtype={self.dtype})"


class PublicState(MutableMapping):
    """`owner.state`: the owner's resident tree (`owner.resident`) read and
    written as the public tree it stands for. The six kernel leaves read as
    `PublicLeaf`; every other leaf is the device array itself. Assigning a
    key writes through to the owner (a public pool is converted once,
    there).

    A pytree whose leaves are the arrays the owner HOLDS: `jax.tree.map`
    and `jax.device_get` work on those — a copy or a fetch of the state
    moves no layout — and give a `PublicState` of their results, which
    reads as the public tree again (`jax.device_get(grp.state)` is a
    checkpoint's input: its leaves re-lay on the host when they are read)
    and which `owner.state = ...` takes back as it is."""

    __slots__ = ("_owner",)

    def __init__(self, owner):
        self._owner = owner

    def __getitem__(self, key):
        x = self._owner.resident[key]
        return PublicLeaf(self._owner, key, x) if key in _KERNEL_KEYS else x

    def __setitem__(self, key, value) -> None:
        owner = self._owner
        owner.resident = resident_tree({**owner.resident, key: value},
                                       owner.cfg.tm, owner)

    def __delitem__(self, key) -> None:
        raise TypeError("a model's state tree drops no leaf")

    def __iter__(self):
        return iter(self._owner.resident)

    def __len__(self) -> int:
        return len(self._owner.resident)


class _Held:
    """The owner of a `PublicState` that no group or runner holds: a mapped
    or fetched copy of one."""

    __slots__ = ("resident", "cfg", "relayouts")

    def __init__(self, resident: dict, cfg):
        self.resident, self.cfg, self.relayouts = resident, cfg, 0


jax.tree_util.register_pytree_node(
    PublicState,
    lambda view: (list(view._owner.resident.values()),
                  (tuple(view._owner.resident), view._owner.cfg)),
    lambda aux, leaves: PublicState(_Held(dict(zip(aux[0], leaves)), aux[1])),
)
