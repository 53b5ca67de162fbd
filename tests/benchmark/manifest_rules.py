"""What a committed cell's test may assert of BENCHMARK.json — written once.

A later PR brings its cell, configuration or per-layer metric as new files
and APPENDED manifest entries, and may edit no file under `paths`, this
directory among them. So a test here that pins a last place or a whole list
shuts every later PR out. The rule, for every test under tests/benchmark/:

  - an entry is found BY NAME (`entry`), never by an index from the end;
  - a shared `workloads` list starts with the cells accepted before this
    one, in their order, holds this cell next, once, and may hold later
    cells after it (`listed_after`; `starts_with` for a metric's own test);
  - a cell's own metrics stand in the order they were added, after every
    metric accepted before them (`added_in_order`) — not "the last three";
  - held exactly: the cell's own entry (`cell_entry`: config, traffic,
    chips, a `why` of at most 200), its configuration's entry, every
    definition <-> manifest agreement (`agrees_with_definition`); the SET of
    metrics a cell reports is held as "at least" (`reports_at_least`).

A cell's manifest assertions are a function of a `Registry`, named
`manifest_holds` in its test file: the cell's own test calls it on
`Registry()`, and the rehearsal (room.py, test_room_for_fields.py) finds
every such function by that name (`manifest_functions`) and calls it on a
copy to which a further configuration, replay cell, live cell and per-layer
metric are appended — a test that pins a last place fails there, in the PR
that writes it."""

from __future__ import annotations

import glob
import importlib
import os

from benchmark.registry import Registry


def entry(entries: list[dict], name: str) -> dict:
    """The one entry of that name, wherever it stands."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, f"{name!r}: {len(found)} entries"
    return found[0]


def starts_with(workloads: list[str], heads) -> None:
    """The accepted cells, in the order they were accepted, come first."""
    heads = list(heads)
    assert workloads[:len(heads)] == heads, (workloads, heads)
    assert len(set(workloads)) == len(workloads), workloads


def listed_after(workloads: list[str], heads, cell: str) -> None:
    """`heads` (every cell the list held when `cell` joined it), then
    `cell`, once; later cells may follow."""
    starts_with(workloads, [*heads, cell])


def added_in_order(per_layer: list[dict], names, after=()) -> None:
    """`names` stand in that order, each after every metric of `after`."""
    place = {m["name"]: i for i, m in enumerate(per_layer)}
    gone = [n for n in [*names, *after] if n not in place]
    assert not gone, gone
    at = [place[n] for n in names]
    assert at == sorted(at), [n for _i, n in sorted(zip(at, names))]
    assert all(place[a] < at[0] for a in after), \
        [a for a in after if place[a] >= at[0]]


def agrees_with_definition(reg: Registry, m: dict) -> dict:
    """The manifest's entry of a per-layer metric says what its definition
    file says, and the reader is there -> the definition."""
    definition, reader = reg.layer_metric(m["name"])
    assert callable(reader.read), m["name"]
    assert (definition["layer"], definition["moves"], definition["unit"]) \
        == (m["layer"], m["moves"], m["unit"]), m["name"]
    return definition


def cell_entry(reg: Registry, cell: str, config: str, traffic: str,
               chips: int = 1) -> dict:
    w = entry(reg.manifest["workloads"], cell)
    assert (w["config"], w["traffic"], w["chips"]) == (config, traffic, chips)
    assert 1 <= len(w["why"]) <= 200
    return w


def reports_at_least(reg: Registry, cell: str, section: str, names) -> dict:
    """-> the cell's metrics of that section by name; a later PR's metric
    may join them, none of `names` may go."""
    got = {m["name"]: m for m in reg.metrics(cell, section)}
    assert set(got) >= set(names), sorted(set(names) - set(got))
    return got


def manifest_functions() -> dict:
    """Every `manifest_holds(reg)` of this directory's test files, by file.
    Called inside a test, never while a module is imported (the cell tests
    import one another's helpers)."""
    found = {}
    for path in sorted(glob.glob(os.path.join(os.path.dirname(__file__),
                                              "test_*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        fn = getattr(importlib.import_module(f"{__package__}.{stem}"),
                     "manifest_holds", None)
        if callable(fn):
            found[stem] = fn
    return found


def committed_cells_hold(reg: Registry) -> None:
    for fn in manifest_functions().values():
        fn(reg)
