"""Partition contracts: every state leaf declares how it lives on the mesh.

Rule ``partition-contract`` (ISSUE 15) — ROADMAP-1's stream-axis
sharding is only safe while every state leaf KNOWS its placement:
``shard-streams`` (the leading G axis splits over the mesh — the SDR
independence property makes this the default for per-stream state),
``replicated`` (every shard holds the full leaf), or ``host-only``
(never device-resident; per-shard process state like the likelihood
moments). An undeclared leaf is exactly the kind of implicit
single-device assumption that turns into silent corruption when a
checkpoint round or journal replay materializes it on the wrong shard.

Rules are DECLARED on the state-tree construction (docs/ANALYSIS.md):

    # rtap: partition[presyn=shard-streams, scores=host-only]   (module)
    "boost": np.ones(C, np.float32),  # rtap: partition[shard-streams]

Constructors are discovered structurally (meshmodel.py): any models/
function building dict literals of numpy/jnp arrays under string keys.
Findings:

* ``<ctor>:unruled:<leaf>`` — a constructed leaf with no declared rule
  (missing coverage);
* ``partition-table:stale:<name>`` — a module-table entry naming no
  constructed leaf (the rule outlived its leaf — coverage must be
  EXACT, both directions);
* ``<qual>:unknown-leaf:<key>`` — a serve-stack consumer subscripting
  a state-like object with a key the declared tree does not contain
  (a renamed leaf whose consumer kept the old string — the drift the
  checkpoint/journal bit-exactness contracts cannot survive);
* ``restore:not-shard-aware`` — some leaf declares ``shard-streams``
  but the checkpoint module never re-places restored state through
  ``shard_state``/``put_sharded`` (a resumed mesh group would silently
  downgrade to single-device);
* ``journal-frame:not-dispatch-routed`` — sharded leaves exist but the
  loop's journal FRAME materialization does not route through
  ``DispatchTable``/``decode_frames_to_row`` (flat-position scatter
  cannot validate shard bits).
"""

from __future__ import annotations

import ast

from rtap_tpu.analysis.core import AnalysisContext, Finding
from rtap_tpu.analysis.meshmodel import build_mesh_model, scopes_of

PASS_NAME = "partition-contract"
PARTITION = "program"
RULES = {
    "partition-contract": "state leaves without a declared partition "
                          "rule, stale rule-table entries, consumers "
                          "touching unknown leaves, and un-shard-aware "
                          "checkpoint/journal wiring",
}

#: serve-stack files whose state subscripts are checked against the
#: declared tree
_CONSUMER_SCOPE = ("rtap_tpu/service/", "rtap_tpu/resilience/",
                   "rtap_tpu/obs/", "rtap_tpu/correlate/")

#: receivers treated as "the state tree" at consumer sites: grp.state / grp.resident,
#: a local st/state/model binding, or the oracle's per-stream _states
_STATE_RECEIVERS = frozenset({"state", "resident", "st", "model", "_states"})

_CHECKPOINT_FILE = "rtap_tpu/service/checkpoint.py"
_LOOP_FILE = "rtap_tpu/service/loop.py"


def _receiver_name(node: ast.AST) -> str | None:
    """Terminal name of a subscript receiver chain: ``grp.state`` ->
    'state', ``self._states[g]`` -> '_states', ``st`` -> 'st'."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _file_references(sf, names: tuple[str, ...]) -> bool:
    return sf.tree is not None and any(n in sf.text for n in names)


def run(ctx: AnalysisContext) -> list[Finding]:
    model = build_mesh_model(ctx)
    out: list[Finding] = list(model.partition_errors)

    # ---- coverage: every constructed leaf carries a rule -------------
    declared: dict[str, set[str]] = {}   # path -> leaf names built there
    for c in model.constructors:
        table = model.partition_tables.get(c.path, {})
        trailing = model.partition_trailing.get(c.path, {})
        names = declared.setdefault(c.path, set())
        for name, line in c.leaves:
            names.add(name)
            if trailing.get(line) is None and name not in table:
                out.append(Finding(
                    rule="partition-contract", path=c.path, line=line,
                    symbol=f"{c.qual}:unruled:{name}",
                    message=f"state leaf {name!r} has no declared "
                            "partition rule — annotate the construction "
                            "with `# rtap: partition[shard-streams|"
                            "replicated|host-only]` (docs/ANALYSIS.md); "
                            "an undeclared leaf is an implicit "
                            "single-device assumption"))

    # ---- exactness: module-table entries must name real leaves -------
    for path, table in model.partition_tables.items():
        built = declared.get(path, set())
        for name, (_rule, line) in sorted(table.items()):
            if name not in built:
                out.append(Finding(
                    rule="partition-contract", path=path, line=line,
                    symbol=f"partition-table:stale:{name}",
                    message=f"partition rule for {name!r} names no leaf "
                            "any constructor in this file builds — the "
                            "rule outlived its leaf; delete or re-key "
                            "it (coverage must be exact)"))

    if not model.leaf_rules:
        return out   # no state trees in this context (fixture subsets)

    # ---- consumers: string-literal leaf touches must resolve ---------
    for sf in ctx.files_under(*_CONSUMER_SCOPE):
        if sf.tree is None:
            continue
        for qual, nodes in scopes_of(sf):
            for node in nodes:
                if not isinstance(node, ast.Subscript):
                    continue
                if not (isinstance(node.slice, ast.Constant)
                        and isinstance(node.slice.value, str)):
                    continue
                if _receiver_name(node.value) not in _STATE_RECEIVERS:
                    continue
                key = node.slice.value
                if key in model.leaf_rules:
                    continue
                out.append(Finding(
                    rule="partition-contract", path=sf.path,
                    line=node.lineno,
                    symbol=f"{qual}:unknown-leaf:{key}",
                    message=f"consumer touches state leaf {key!r} that "
                            "no models/ constructor declares — a "
                            "renamed/removed leaf whose consumer kept "
                            "the old string would desynchronize "
                            "checkpoint/journal replay"))

    # ---- wiring gates: sharded leaves demand shard-aware plumbing ----
    if any(r == "shard-streams" for r in model.leaf_rules.values()):
        ck = ctx.file(_CHECKPOINT_FILE)
        if ck is not None and not _file_references(
                ck, ("shard_state", "put_sharded")):
            out.append(Finding(
                rule="partition-contract", path=_CHECKPOINT_FILE, line=1,
                symbol="restore:not-shard-aware",
                message="leaves declare shard-streams but the "
                        "checkpoint module never re-places restored "
                        "state via shard_state/put_sharded — a resumed "
                        "mesh group would silently downgrade to "
                        "single-device"))
        lp = ctx.file(_LOOP_FILE)
        if lp is not None and not _file_references(
                lp, ("DispatchTable",)):
            out.append(Finding(
                rule="partition-contract", path=_LOOP_FILE, line=1,
                symbol="journal-frame:not-dispatch-routed",
                message="leaves declare shard-streams but the loop's "
                        "journal FRAME materialization does not route "
                        "through DispatchTable — flat-position scatter "
                        "cannot reject wrong-shard addressing"))
    return out
