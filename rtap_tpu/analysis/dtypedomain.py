"""Dtype-domain discipline: quantized permanences and i32 keys never
mix silently.

Rule ``dtype-domain`` — the u16→u8 permanence migration (ROADMAP-3,
grounded in the low-precision-HTM results of PAPERS 1803.05131 /
1812.10730) is only safe while every piece of arithmetic knows which
grid it is on: a u8 quantum added to a u16 quantum is a value bug no
dtype system catches (both sides are "just ints" by the time XLA sees
them), and i32 key arithmetic (``cat * w + k``) wraps on device where
host i64 silently would not — the exact class PR 9's categorical
double-clamp fixed by hand.

Domains are DECLARED, not inferred — a small annotation table per file
(docs/ANALYSIS.md):

    # rtap: domain[perm=u16, syn_perm=u16, keys=i32-key]     (module-wide)
    buckets = ...  # rtap: domain[i32-key]                    (this binding)

Module-wide entries bind variable names AND ``state["<name>"]``
subscript keys; the trailing form binds that assignment's targets.
Valid domains: ``u8 | u16 | i32-key``. Three findings:

* ``<qual>:mix:<a>~<b>`` — a binary op whose operands carry DIFFERENT
  declared domains with no explicit ``astype`` widening at the site;
* ``<qual>:i32-wrap:<v>`` — multiplication of an ``i32-key`` value
  that is not clamp-protected (produced by ``jnp.clip``/``np.clip``
  somewhere in its chain) — the add in ``bucket + arange`` is fine,
  the multiply in ``cat * w`` is where a wild category id wraps;
* ``<qual>:undeclared:<dtype>`` — a literal cast onto a quantized grid
  (``astype(jnp.uint8 | uint16)``) over a value with no declared
  domain: the cast invents a domain the table never heard of.

Scope: ``rtap_tpu/ops/``, ``rtap_tpu/models/`` and ``scripts/``
(eval scaffolding builds quantized state too).
An ``astype`` whose target dtype is non-literal (``dom.compute_dtype``)
is the sanctioned domain-polymorphic idiom (models/perm.py) and clears
the operand's domain rather than guessing one.
"""

from __future__ import annotations

import ast
import re

from rtap_tpu.analysis.core import AnalysisContext, Finding, SourceFile
from rtap_tpu.analysis.kernels import dotted, functions_in, \
    stmt_expr_nodes

PASS_NAME = "dtype-domain"
PARTITION = "file"
RULES = {
    "dtype-domain": "cross-domain arithmetic without a widening cast, "
                    "unclamped i32-key multiplication, or a quantized "
                    "cast onto an undeclared domain",
}

_DOMAINS = ("u8", "u16", "i32-key")

_MODULE_RE = re.compile(
    r"#\s*rtap:\s*domain\[([A-Za-z_][\w]*\s*=\s*[\w-]+"
    r"(?:\s*,\s*[A-Za-z_][\w]*\s*=\s*[\w-]+)*)\]")
_TRAILING_RE = re.compile(r"#\s*rtap:\s*domain\[([\w-]+)\]")

#: literal cast targets that land on a quantized grid
_GRID_DTYPES = {"uint8": "u8", "uint16": "u16"}

_SCOPES = ("rtap_tpu/ops/", "rtap_tpu/models/", "scripts/")


def file_domain_table(sf: SourceFile) -> tuple[dict[str, str],
                                               dict[int, str],
                                               list[Finding]]:
    """(module-wide name->domain, lineno->domain for trailing form,
    syntax findings for unknown domain tokens)."""
    table: dict[str, str] = {}
    trailing: dict[int, str] = {}
    bad: list[Finding] = []
    for i, line in enumerate(sf.lines, start=1):
        m = _MODULE_RE.search(line)
        if m:
            for pair in m.group(1).split(","):
                name, dom = (s.strip() for s in pair.split("="))
                if dom not in _DOMAINS:
                    bad.append(Finding(
                        rule="dtype-domain", path=sf.path, line=i,
                        symbol=f"domain-syntax:{name}",
                        message=f"unknown domain '{dom}' — valid: "
                                f"{', '.join(_DOMAINS)}"))
                else:
                    table[name] = dom
            continue
        m = _TRAILING_RE.search(line)
        if m:
            dom = m.group(1)
            if dom not in _DOMAINS:
                bad.append(Finding(
                    rule="dtype-domain", path=sf.path, line=i,
                    symbol="domain-syntax:trailing",
                    message=f"unknown domain '{dom}' — valid: "
                            f"{', '.join(_DOMAINS)}"))
            else:
                trailing[i] = dom
    return table, trailing, bad


def _astype_target(call: ast.Call) -> str | None:
    """'u8'/'u16'/'i32-key' for a literal astype target, '' for a
    non-literal (domain-polymorphic) one, None if not an astype."""
    if not (isinstance(call.func, ast.Attribute)
            and call.func.attr == "astype" and call.args):
        return None
    d = dotted(call.args[0])
    if d is None:
        return ""
    leaf = d.rsplit(".", 1)[-1]
    if leaf in _GRID_DTYPES:
        return _GRID_DTYPES[leaf]
    if leaf == "int32":
        return "i32-key"
    # int64 is the HOST's wrap-safe widening (the oracle idiom) — it
    # clears the key domain rather than entering it
    return ""


class _Expr:
    """Domain + clamp provenance of one expression."""

    __slots__ = ("domain", "clamped", "name")

    def __init__(self, domain=None, clamped=False, name=None):
        self.domain = domain
        self.clamped = clamped
        self.name = name


def _eval(node: ast.AST, names: dict[str, "_Expr"],
          table: dict[str, str]) -> "_Expr":
    """Bottom-up domain evaluation of one expression."""
    if isinstance(node, ast.Name):
        if node.id in names:
            e = names[node.id]
            return _Expr(e.domain, e.clamped, node.id)
        if node.id in table:
            return _Expr(table[node.id], False, node.id)
        return _Expr()
    if isinstance(node, ast.Subscript):
        # state["perm"]-style access adopts the key's declared domain
        if isinstance(node.slice, ast.Constant) \
                and isinstance(node.slice.value, str) \
                and node.slice.value in table:
            return _Expr(table[node.slice.value], False,
                         node.slice.value)
        return _eval(node.value, names, table)
    if isinstance(node, ast.Call):
        t = _astype_target(node)
        if t is not None:
            inner = _eval(node.func.value, names, table)
            # explicit cast: re-domains (literal) or clears (dynamic)
            return _Expr(t or None, inner.clamped, inner.name)
        d = dotted(node.func)
        leaf = d.rsplit(".", 1)[-1] if d else None
        if leaf == "clip":
            # module form clip(x, lo, hi) vs method form x.clip(lo, hi)
            if d in ("jnp.clip", "np.clip", "numpy.clip",
                     "jax.numpy.clip") and node.args:
                inner = _eval(node.args[0], names, table)
            elif isinstance(node.func, ast.Attribute):
                inner = _eval(node.func.value, names, table)
            else:
                inner = _Expr()
            return _Expr(inner.domain, True, inner.name)
        if leaf in ("where", "round", "minimum", "maximum", "abs"):
            doms = [_eval(a, names, table) for a in node.args]
            for e in doms:
                if e.domain is not None:
                    return _Expr(e.domain,
                                 all(x.clamped or x.domain is None
                                     for x in doms), e.name)
        return _Expr()
    if isinstance(node, ast.BinOp):
        left = _eval(node.left, names, table)
        right = _eval(node.right, names, table)
        dom = left.domain or right.domain
        return _Expr(dom, left.clamped or right.clamped,
                     left.name or right.name)
    if isinstance(node, ast.UnaryOp):
        return _eval(node.operand, names, table)
    return _Expr()


def _own_statements(fn: ast.FunctionDef):
    """fn's statements in source order, recursing into compound
    statements but not nested defs (those get their own qualnames)."""
    def rec(body):
        for st in body:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            yield st
            for attr in ("body", "orelse", "finalbody"):
                yield from rec(getattr(st, attr, []))
            for h in getattr(st, "handlers", []):
                yield from rec(h.body)

    yield from rec(fn.body)


def run(ctx: AnalysisContext) -> list[Finding]:
    out: list[Finding] = []
    for sf in ctx.files_under(*_SCOPES):
        if sf.tree is None:
            continue
        table, trailing, bad = file_domain_table(sf)
        out.extend(bad)
        for qual, fn in functions_in(sf.tree):
            names: dict[str, _Expr] = {}
            for st in _own_statements(fn):
                for node in stmt_expr_nodes(st):
                    # ---- mixes + unclamped key multiplies -----------
                    if isinstance(node, ast.BinOp):
                        _check_arith(
                            _eval(node.left, names, table),
                            _eval(node.right, names, table),
                            node.op, node.lineno, qual, sf, out)
                    # ---- casts onto undeclared quantized grids ------
                    elif isinstance(node, ast.Call):
                        t = _astype_target(node)
                        if t in ("u8", "u16") \
                                and trailing.get(node.lineno) != t:
                            inner = _eval(node.func.value, names, table)
                            if inner.domain is None:
                                out.append(Finding(
                                    rule="dtype-domain", path=sf.path,
                                    line=node.lineno,
                                    symbol=f"{qual}:undeclared:{t}",
                                    message=f"literal cast onto the "
                                            f"{t} grid over a value "
                                            "with no declared domain "
                                            "— add it to the file's "
                                            "`# rtap: domain[...]` "
                                            "table so mixes stay "
                                            "machine-checkable"))
                # in-place updates are arithmetic too: `perm += d`
                # is the permanence-update idiom the u16->u8 rail
                # exists for, and it never shows up as a BinOp
                if isinstance(st, ast.AugAssign):
                    left = _eval(st.target, names, table)
                    right = _eval(st.value, names, table)
                    _check_arith(left, right, st.op, st.lineno, qual,
                                 sf, out)
                    if isinstance(st.target, ast.Name):
                        names[st.target.id] = _Expr(
                            left.domain or right.domain,
                            left.clamped and right.clamped,
                            st.target.id)
                # ---- bind AFTER checking (RHS uses prior names) -----
                if isinstance(st, ast.Assign) and st.value is not None:
                    e = _eval(st.value, names, table)
                    decl = trailing.get(st.lineno)
                    if decl is not None:
                        e = _Expr(decl, e.clamped, e.name)
                    for t in st.targets:
                        if isinstance(t, ast.Name):
                            names[t.id] = e
    return out


def _check_arith(left: "_Expr", right: "_Expr", op: ast.operator,
                 lineno: int, qual: str, sf, out: list[Finding]) -> None:
    """The mix / i32-wrap judgment for one binary operation — shared by
    BinOp expressions and AugAssign statements."""
    if left.domain and right.domain and left.domain != right.domain:
        a, b = sorted((left.domain, right.domain))
        out.append(Finding(
            rule="dtype-domain", path=sf.path, line=lineno,
            symbol=f"{qual}:mix:{a}~{b}",
            message=f"arithmetic mixes domains {left.domain} and "
                    f"{right.domain} with no explicit widening cast — "
                    "quanta on different grids are different VALUES; "
                    "astype through the compute domain first "
                    "(models/perm.py)"))
    elif isinstance(op, ast.Mult):
        for side in (left, right):
            if side.domain == "i32-key" and not side.clamped:
                out.append(Finding(
                    rule="dtype-domain", path=sf.path, line=lineno,
                    symbol=f"{qual}:i32-wrap:{side.name or 'expr'}",
                    message="multiplying an unclamped i32-key value — "
                            "device i32 wraps where host i64 would "
                            "not (the PR 9 categorical class); clamp "
                            "to the key bound first"))
                break
