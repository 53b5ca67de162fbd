"""Scaling math: SCALING.md's bytes/stream numbers, re-derived statically.

Rule ``scaling-math`` (ISSUE 15) — the flag-docs/metric-catalog gate's
memory twin. SCALING.md's analytic table (bytes/stream per permanence
domain, max streams/chip, largest tensors) is the number every capacity
decision on ROADMAP-3's 50k→100k ladder stands on, and it is generated
by running ``state_nbytes`` — so a config edit (pool sizes, encoder
width) silently stales the committed doc until someone reruns
``scripts/scaling_law.py``. This pass re-derives the same numbers from
PURE AST:

* geometry from ``cluster_preset``'s literal arguments in config.py
  (dataclass defaults fill unspecified fields);
* the per-leaf byte formulas of the models/state.py layout (the same
  shapes the partition contract covers);
* quantized-grid byte widths from models/perm.py's dtype table — the
  v3 dtype-domain pass's ground truth, so the two rails can't disagree;
* the HBM budget constants from scripts/scaling_law.py.

and cross-checks every quoted figure. A mismatch means the doc is stale
(or the derivation wrong — either way a human must look): finding
symbols ``bytes:<domain>``, ``fit:<domain>``, ``tensor:<name>``,
``derive:<what>`` (inputs present but underivable).
"""

from __future__ import annotations

import ast
import os
import re

from rtap_tpu.analysis.core import AnalysisContext, Finding

PASS_NAME = "scaling-math"
PARTITION = "program"
RULES = {
    "scaling-math": "SCALING.md bytes/stream, streams/chip, and "
                    "largest-tensor figures cross-checked against a "
                    "static derivation from the config dataclasses",
}

_CONFIG = "rtap_tpu/config.py"
_PERM = "rtap_tpu/models/perm.py"
_LAW = "scripts/scaling_law.py"

#: SCALING.md analytic-table row: | <domain> | <bytes> | <fit> |
_ROW_RE = re.compile(
    r"^\|\s*(f32|u16 quanta|u8 quanta)\s*\|\s*([\d,]+)\s*\|"
    r"\s*([\d,]+)\s*\|")
_TENSOR_LINE_RE = re.compile(r"^Largest tensors \(u16 domain\):(.*)$")
_TENSOR_RE = re.compile(r"`?(\w+)`?\s+([\d,]+)\s*B")

_DOMAIN_BITS = {"f32": 0, "u16 quanta": 16, "u8 quanta": 8}
_DTYPE_BYTES = {"float32": 4, "uint16": 2, "uint8": 1}


def _const_eval(node: ast.AST):
    """Evaluate a numeric constant expression (16 * 1024**3 style)."""
    if isinstance(node, ast.Constant) and isinstance(
            node.value, (int, float)):
        return node.value
    if isinstance(node, ast.BinOp):
        left, right = _const_eval(node.left), _const_eval(node.right)
        if left is None or right is None:
            return None
        ops = {ast.Add: lambda a, b: a + b, ast.Sub: lambda a, b: a - b,
               ast.Mult: lambda a, b: a * b, ast.Pow: lambda a, b: a ** b,
               ast.FloorDiv: lambda a, b: a // b,
               ast.Div: lambda a, b: a / b}
        fn = ops.get(type(node.op))
        return fn(left, right) if fn else None
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = _const_eval(node.operand)
        return -inner if inner is not None else None
    return None


def _dataclass_defaults(tree: ast.AST, cls: str) -> dict:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls:
            out = {}
            for st in node.body:
                if isinstance(st, ast.AnnAssign) and st.value is not None \
                        and isinstance(st.target, ast.Name):
                    v = _const_eval(st.value)
                    if v is not None:
                        out[st.target.id] = v
            return out
    return {}


def _preset_kwargs(tree: ast.AST, sub: str) -> dict | None:
    """Literal keyword args of the ``<sub>Config(...)`` call inside
    ``cluster_preset``'s returned ModelConfig (None: not found)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) \
                and node.name == "cluster_preset":
            for call in ast.walk(node):
                if isinstance(call, ast.Call) \
                        and isinstance(call.func, ast.Name) \
                        and call.func.id == sub:
                    out = {}
                    for kw in call.keywords:
                        v = _const_eval(kw.value)
                        if kw.arg is not None and v is not None:
                            out[kw.arg] = v
                    return out
    return None


def _perm_bytes_table(perm_sf) -> dict[int, int] | None:
    """bits -> storage bytes, read from models/perm.py's dtype dict
    (``{0: np.float32, 8: np.uint8, 16: np.uint16}``) — the same table
    the v3 dtype-domain declarations quantize onto."""
    if perm_sf is None or perm_sf.tree is None:
        return None
    for node in ast.walk(perm_sf.tree):
        if not isinstance(node, ast.Dict) or len(node.keys) < 3:
            continue
        out = {}
        for k, v in zip(node.keys, node.values):
            if not (isinstance(k, ast.Constant)
                    and isinstance(k.value, int)):
                break
            name = None
            if isinstance(v, ast.Attribute):
                name = v.attr
            if name not in _DTYPE_BYTES:
                break
            out[k.value] = _DTYPE_BYTES[name]
        else:
            if {0, 8, 16} <= set(out):
                return out
    return None


def _law_constants(law_sf) -> tuple[float, float] | None:
    if law_sf is None or law_sf.tree is None:
        return None
    hbm = reserve = None
    for node in ast.walk(law_sf.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            if node.targets[0].id == "HBM_BYTES":
                hbm = _const_eval(node.value)
            elif node.targets[0].id == "WORKSPACE_RESERVE":
                reserve = _const_eval(node.value)
    if hbm is None or reserve is None:
        return None
    return hbm, reserve


def derive_leaf_bytes(cfg_sf, perm_sf, bits: int) -> dict[str, int] | None:
    """Per-leaf byte sizes of one cluster-preset stream at permanence
    domain `bits` — the models/state.py layout, derived statically."""
    if cfg_sf is None or cfg_sf.tree is None:
        return None
    tree = cfg_sf.tree
    perm_b = _perm_bytes_table(perm_sf)
    if perm_b is None or bits not in perm_b:
        return None
    sp = _preset_kwargs(tree, "SPConfig")
    tm = _preset_kwargs(tree, "TMConfig")
    rdse = _preset_kwargs(tree, "RDSEConfig")
    date = _preset_kwargs(tree, "DateConfig")
    if sp is None or tm is None or rdse is None:
        return None
    sp = {**_dataclass_defaults(tree, "SPConfig"), **sp}
    tm = {**_dataclass_defaults(tree, "TMConfig"), **tm}
    rdse = {**_dataclass_defaults(tree, "RDSEConfig"), **rdse}
    date = {**_dataclass_defaults(tree, "DateConfig"), **(date or {})}
    try:
        C = int(sp["columns"])
        K = int(tm["cells_per_column"])
        S = int(tm["max_segments_per_cell"])
        M = int(tm["max_synapses_per_segment"])
        rdse_size = int(rdse["size"])
        date_size = (int(date["time_of_day_size"])
                     if date.get("time_of_day_width") else 0) \
            + int(date.get("weekend_width", 0))
    except (KeyError, TypeError, ValueError):
        return None
    n_fields = 1   # cluster_preset leaves ModelConfig.n_fields default
    nin = rdse_size * n_fields + date_size
    cells, segs, pool = C * K, C * K * S, C * K * S * M
    presyn_b = 2 if cells <= (1 << 15) - 1 else 4
    pb = perm_b[bits]
    if bool(sp.get("sparse_pool", False)):
        # member-index layout (ISSUE 18): members i16/i32 [C, P] + perm
        # [C, P] replace the dense potential/perm plane; P mirrors
        # ModelConfig.sp_members (pool_members pin wins, else the
        # round-half-up potential fraction) and the index dtype mirrors
        # models/state.py members_dtype
        P = int(sp.get("pool_members", 0) or 0) or int(float(sp["potential_pct"]) * nin + 0.5)
        members_b = 2 if nin <= (1 << 15) - 1 else 4
        sp_leaves = {"members": C * P * members_b, "perm": C * P * pb}
    else:
        sp_leaves = {"potential": C * nin, "perm": C * nin * pb}
    return {
        **sp_leaves,
        "boost": C * 4, "overlap_duty": C * 4, "active_duty": C * 4,
        "sp_iter": 4,
        "presyn": pool * presyn_b, "syn_perm": pool * pb,
        "seg_last": segs * 4, "active_seg": segs, "matching_seg": segs,
        "seg_pot": segs * 2, "prev_active": cells, "prev_winner": cells,
        "tm_iter": 4, "tm_overflow": 4,
        "enc_offset": n_fields * 4, "enc_bound": n_fields,
        "enc_resolution": n_fields * 4,
    }


def derived_stream_bytes(root: str, bits: int) -> int | None:
    """Analyzer-derived bytes/stream of one cluster-preset stream, read
    from the REAL repo files under `root` (None when underivable). This is
    the same static derivation the SCALING.md gate runs;
    tests/integration/test_bringup.py holds the real arrays' byte sum
    equal to it, and chip_smoke.py sizes its serve verdict by it, so a
    layout change that moves real bytes without moving the doc twin fails
    loudly instead of drifting (ISSUE 18 satellite 5)."""
    from rtap_tpu.analysis.core import SourceFile

    sfs = []
    for rel in (_CONFIG, _PERM):
        try:
            with open(os.path.join(root, rel), encoding="utf-8") as fh:
                sfs.append(SourceFile(rel, fh.read()))
        except OSError:
            return None
    leaves = derive_leaf_bytes(sfs[0], sfs[1], bits)
    return None if leaves is None else sum(leaves.values())


def run(ctx: AnalysisContext) -> list[Finding]:
    text = ctx.scaling()
    if not text:
        return []
    lines = text.splitlines()
    rows: list[tuple[str, int, int, int]] = []   # domain, bytes, fit, ln
    tensor_line: tuple[str, int] | None = None
    for i, line in enumerate(lines, start=1):
        m = _ROW_RE.match(line.strip())
        if m:
            rows.append((m.group(1), int(m.group(2).replace(",", "")),
                         int(m.group(3).replace(",", "")), i))
        m = _TENSOR_LINE_RE.match(line.strip())
        if m:
            tensor_line = (m.group(1), i)
    if not rows and tensor_line is None:
        return []   # no analytic table to check (fixture contexts)

    out: list[Finding] = []
    cfg_sf = ctx.file(_CONFIG)
    perm_sf = ctx.file(_PERM)
    law = _law_constants(ctx.file(_LAW))
    per_domain = {bits: derive_leaf_bytes(cfg_sf, perm_sf, bits)
                  for bits in (0, 16, 8)}
    if any(v is None for v in per_domain.values()):
        out.append(Finding(
            rule="scaling-math", path="SCALING.md", line=1,
            symbol="derive:inputs",
            message="SCALING.md quotes an analytic bytes/stream table "
                    "but the cluster-preset geometry could not be "
                    "derived from rtap_tpu/config.py + models/perm.py "
                    "— the doc's memory twin is blind; restore the "
                    "literal preset/dtype tables"))
        return out

    for domain, quoted_bytes, quoted_fit, ln in rows:
        bits = _DOMAIN_BITS[domain]
        derived = sum(per_domain[bits].values())
        if derived != quoted_bytes:
            out.append(Finding(
                rule="scaling-math", path="SCALING.md", line=ln,
                symbol=f"bytes:{domain.split()[0]}",
                message=f"quoted {quoted_bytes:,} bytes/stream for "
                        f"{domain} but the config derives "
                        f"{derived:,} — the table is stale; rerun "
                        "scripts/scaling_law.py"))
        elif law is not None:
            hbm, reserve = law
            fit = int((hbm - reserve) // derived)
            if fit != quoted_fit:
                out.append(Finding(
                    rule="scaling-math", path="SCALING.md", line=ln,
                    symbol=f"fit:{domain.split()[0]}",
                    message=f"quoted {quoted_fit:,} streams/chip for "
                            f"{domain} but (HBM - reserve) // "
                            f"bytes = {fit:,} — the capacity column "
                            "is stale"))

    if tensor_line is not None:
        rest, ln = tensor_line
        u16 = per_domain[16]
        for name, num in _TENSOR_RE.findall(rest):
            quoted = int(num.replace(",", ""))
            if name in u16 and u16[name] != quoted:
                out.append(Finding(
                    rule="scaling-math", path="SCALING.md", line=ln,
                    symbol=f"tensor:{name}",
                    message=f"largest-tensor line quotes {name} at "
                            f"{quoted:,} B but the config derives "
                            f"{u16[name]:,} B"))
            elif name not in u16:
                out.append(Finding(
                    rule="scaling-math", path="SCALING.md", line=ln,
                    symbol=f"tensor:{name}",
                    message=f"largest-tensor line names {name!r} which "
                            "the derived state layout does not "
                            "contain — a renamed leaf left the doc "
                            "behind"))
    return out
