"""Single-device idioms in the serve stack: the ROADMAP-1 inventory pass.

Rule ``device-scope`` (ISSUE 15) — the serve stack grew up on one chip
and it shows: ``jax.local_devices()[0]`` reads, blanket ``device_get``
fetches of possibly-sharded state, and flat-stream-id arithmetic that
bypasses the registry's ``SlotAddress{shard, group, slot}`` addressing.
Each one is harmless today and a silent wrong-shard read (or a full
cross-mesh gather on the hot path) the day the fleet spans a v5e-8.
Three findings:

* ``<qual>:device0`` — subscripting ``jax.devices()``/
  ``jax.local_devices()`` (the [0] idiom): on a mesh there is no "the"
  device; iterate or aggregate instead. Declared mesh entry points are
  exempt — they own placement, and picking a device BY SHARD INDEX is
  exactly what the ``# rtap: mesh-entry`` annotation legalizes;
* ``<qual>:fetch:<what>`` — ``jax.device_get(...)`` anywhere, or
  ``np.asarray``/``np.array`` over a state-rooted expression, OUTSIDE a
  declared host boundary (``# rtap: host-boundary — why`` on the def,
  the twin[...] placement grammar; mesh entry points are boundaries by
  construction). Fetching sharded values is legal only where placement
  is owned — everywhere else it is an implicit single-device gather;
* ``<qual>:flat-id:<name>`` — stream/slot arithmetic against group or
  shard extents (``sid // group_size``-shaped), or slot-code bit
  surgery (``SLOT_BITS``/``MAX_*`` masks/shifts) outside the blessed
  addressing modules (service/registry.py, ingest/protocol.py,
  ingest/dispatch.py) — the ONLY places allowed to know how a flat id
  maps onto (shard, group, slot).
"""

from __future__ import annotations

import ast
import re

from rtap_tpu.analysis.core import AnalysisContext, Finding
from rtap_tpu.analysis.kernels import dotted
from rtap_tpu.analysis.meshmodel import build_mesh_model, scopes_of

PASS_NAME = "device-scope"
PARTITION = "file"
RULES = {
    "device-scope": "single-device idioms in the serve stack: "
                    "devices()[0] reads, device fetches outside "
                    "declared host boundaries, flat-stream-id "
                    "arithmetic bypassing SlotAddress",
}

#: the serve stack (ops/ hot-path fetches are the purity pass's beat)
#: plus the operator tools — scripts' devices()[0] platform probes and
#: fetches are exactly the single-device assumptions the ROADMAP-1
#: inventory must track (each is baselined with a why or fixed)
_SCOPES = ("rtap_tpu/service/", "rtap_tpu/resilience/", "rtap_tpu/obs/",
           "rtap_tpu/correlate/", "rtap_tpu/ingest/",
           "rtap_tpu/__main__.py", "scripts/")

#: the addressing owners: flat-id <-> SlotAddress conversion lives here
#: and nowhere else
_ADDRESSING_OWNERS = ("rtap_tpu/service/registry.py",
                      "rtap_tpu/ingest/protocol.py",
                      "rtap_tpu/ingest/dispatch.py")

#: names whose subscript/attr chains mark an expression "possibly
#: sharded": the group state tree and its common local bindings
_STATE_ROOTS = frozenset({"state", "st", "_states"})

#: slot-code constants only the addressing owners may shift/mask with
_CODE_CONSTS = frozenset({"SLOT_BITS", "GROUP_BITS", "SHARD_BITS",
                          "MAX_SLOTS", "MAX_GROUPS", "MAX_SHARDS"})

_STREAMY_RE = re.compile(
    r"(?:^|_)(?:sid|sids|stream|streams|slot|slots|idx|pos|code|codes)"
    r"(?:$|_)")
_EXTENT_RE = re.compile(
    r"(?:^|\.)(?:group_size|n_groups|num_groups|n_shards|num_shards|"
    r"shards)$")


def _mentions_state(node: ast.AST) -> str | None:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _STATE_ROOTS:
            return sub.attr
        if isinstance(sub, ast.Name) and sub.id in _STATE_ROOTS:
            return sub.id
    return None


def _side_name(node: ast.AST) -> str | None:
    """The name a BinOp side is 'about': its dotted chain's leaf."""
    d = dotted(node)
    if d is not None:
        return d
    if isinstance(node, ast.Subscript):
        return _side_name(node.value)
    return None


def run(ctx: AnalysisContext) -> list[Finding]:
    model = build_mesh_model(ctx)
    out: list[Finding] = []
    for sf in ctx.files_under(*_SCOPES):
        if sf.tree is None:
            continue
        owner = sf.path in _ADDRESSING_OWNERS
        for qual, nodes in scopes_of(sf):
            boundary = model.is_host_boundary(sf.path, qual)
            # entry points own placement in both directions — a
            # declared mesh entry picking a device BY SHARD INDEX is
            # exactly what the annotation legalizes (docs/ANALYSIS.md)
            entry = model.is_entry(sf.path, qual)
            for node in nodes:
                # ---- devices()[k] ------------------------------------
                if isinstance(node, ast.Subscript) \
                        and isinstance(node.value, ast.Call):
                    d = dotted(node.value.func)
                    if d in ("jax.devices", "jax.local_devices") \
                            and not entry:
                        out.append(Finding(
                            rule="device-scope", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:device0",
                            message=f"indexing {d}() assumes one "
                                    "canonical device — on a mesh "
                                    "there is no [0]; iterate/"
                                    "aggregate over the device list "
                                    "or take the mesh as input"))
                # ---- fetches outside host boundaries -----------------
                elif isinstance(node, ast.Call):
                    d = dotted(node.func)
                    leaf = d.rsplit(".", 1)[-1] if d else None
                    if d == "jax.device_get" and not boundary:
                        out.append(Finding(
                            rule="device-scope", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:fetch:device_get",
                            message="device_get outside a declared "
                                    "host boundary — under a mesh this "
                                    "is a full cross-shard gather; "
                                    "mark the function `# rtap: "
                                    "host-boundary — why` if it owns "
                                    "the materialization, or move the "
                                    "fetch behind one that does"))
                    elif leaf in ("asarray", "array") and d is not None \
                            and d.split(".", 1)[0] in ("np", "numpy") \
                            and node.args and not boundary:
                        root = _mentions_state(node.args[0])
                        if root is not None:
                            out.append(Finding(
                                rule="device-scope", path=sf.path,
                                line=node.lineno,
                                symbol=f"{qual}:fetch:{root}",
                                message=f"np.{leaf} over the state "
                                        "tree outside a declared host "
                                        "boundary — an implicit "
                                        "device->host gather of a "
                                        "possibly-sharded leaf; "
                                        "annotate the boundary or "
                                        "fetch through one"))
                # ---- flat-id arithmetic ------------------------------
                elif isinstance(node, ast.BinOp) and not owner:
                    lname = _side_name(node.left) or ""
                    rname = _side_name(node.right) or ""
                    if isinstance(node.op, (ast.FloorDiv, ast.Mod,
                                            ast.Mult)):
                        pairs = ((lname, rname), (rname, lname))
                        for a, b in pairs:
                            if _STREAMY_RE.search(a.rsplit(".", 1)[-1]) \
                                    and _EXTENT_RE.search(b):
                                out.append(Finding(
                                    rule="device-scope", path=sf.path,
                                    line=node.lineno,
                                    symbol=f"{qual}:flat-id:"
                                           f"{a.rsplit('.', 1)[-1]}",
                                    message="flat-stream-id arithmetic "
                                            "against a group/shard "
                                            "extent — placement math "
                                            "belongs to SlotAddress "
                                            "(service/registry.py, "
                                            "ingest/dispatch.py), not "
                                            "call sites"))
                                break
                    elif isinstance(node.op, (ast.LShift, ast.RShift,
                                              ast.BitAnd, ast.BitOr)):
                        for side in (lname, rname):
                            if side.rsplit(".", 1)[-1] in _CODE_CONSTS:
                                out.append(Finding(
                                    rule="device-scope", path=sf.path,
                                    line=node.lineno,
                                    symbol=f"{qual}:flat-id:"
                                           f"{side.rsplit('.', 1)[-1]}",
                                    message="slot-code bit surgery "
                                            "outside the addressing "
                                            "owners — only ingest/"
                                            "protocol.py may know the "
                                            "shard|group|slot packing"))
                                break
    return out
