"""Everything the harness runs, found by name — never by an edit here.

    a cell            an entry of BENCHMARK.json's `workloads`
    a configuration   benchmark/configs/<config>.json
    a traffic mix     benchmark/traffic/<traffic>.json; its `kind` names
                      the generator code benchmark/traffic_kinds/<kind>.py
    a per-layer metric  benchmark/layer_metrics/<name>.json, which names its
                      reader benchmark/readers/<reader>.py

A later PR adds any of them as new files (and one entry of BENCHMARK.json);
a name that cannot be found fails loudly, naming the file looked for. What a
cell's test may assert of the manifest — entries by name, a shared list's
accepted cells first and in their order, never a last place or a whole
list — is written once, in tests/benchmark/manifest_rules.py."""

from __future__ import annotations

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NotFound(LookupError):
    """A cell, configuration, traffic mix, kind, metric or reader that
    BENCHMARK.json or a data file names does not exist."""


class Registry:
    """Lookups under `root` (a checkout: the directory that holds
    BENCHMARK.json and benchmark/)."""

    def __init__(self, root: str = REPO):
        self.root = root
        self.dir = os.path.join(root, "benchmark")
        path = os.path.join(root, "BENCHMARK.json")
        if not os.path.isfile(path):
            raise NotFound(f"no BENCHMARK.json at {path}")
        with open(path) as f:
            self.manifest = json.load(f)

    def _json(self, sub: str, name: str) -> dict:
        path = os.path.join(self.dir, sub, name + ".json")
        if not os.path.isfile(path):
            raise NotFound(f"{sub[:-1] if sub.endswith('s') else sub} "
                           f"{name!r}: no file {path}")
        with open(path) as f:
            return json.load(f)

    def _module(self, sub: str, name: str):
        path = os.path.join(self.dir, sub, name + ".py")
        if not os.path.isfile(path):
            raise NotFound(f"{sub} {name!r}: no file {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{sub}.{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def cell(self, name: str) -> dict:
        """-> {name, chips, config: {...}, traffic: {...}, kind: module}."""
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                traffic = self._json("traffic", w["traffic"])
                return {"name": name, "chips": w["chips"],
                        "config": self._json("configs", w["config"]),
                        "traffic": traffic,
                        "kind": self._module("traffic_kinds", traffic["kind"])}
        raise NotFound(
            f"cell {name!r} is not in BENCHMARK.json's workloads "
            f"({[w['name'] for w in self.manifest['workloads']]})")

    def metrics(self, cell: str, section: str) -> list[dict]:
        """BENCHMARK.json's `end_to_end` or `per_layer` entries that this
        cell reports (an entry without `workloads` is reported everywhere
        its `moves` metric is)."""
        e2e = {m["name"]: m for m in self.manifest["end_to_end"]}

        def reported(m: dict) -> bool:
            if "workloads" in m:
                return cell in m["workloads"]
            return "moves" not in m or reported(e2e[m["moves"]])

        return [m for m in self.manifest[section] if reported(m)]

    def layer_metric(self, name: str):
        """-> (definition dict, reader module) of a per-layer metric."""
        definition = self._json("layer_metrics", name)
        return definition, self._module("readers", definition["reader"])
