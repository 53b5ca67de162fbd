"""Trace safety: no data-dependent Python control flow in traced code.

Rule ``trace-safety`` — purity (ISSUE 12) bans host *syncs* in kernel
code; this pass (ISSUE 14) extends the scope to host *decisions*. A
``bool()``/``int()``/``float()`` or an ``if`` on a value flowing from a
traced operand is a TracerError under jit at best — and at worst it
traces "successfully" on the first concrete call and silently bakes one
branch into the compiled program. These must be machine findings, not
review catches. Four shapes, all inside traced
``rtap_tpu/ops/`` functions (traced = calls into jnp/lax/pl):

* ``if``/``while`` whose test reads a *tainted* name —
  symbol ``<qual>:if-on-traced:<var>``;
* ``bool()``/``int()``/``float()`` (or ``.tolist()``) over a tainted
  value — symbol ``<qual>:py-cast:<fn>``;
* ``np.*`` calls fed a tainted value (a host round-trip beyond the
  purity-fetch set) — symbol ``<qual>:host-call:<fn>``;
* data-dependent output shapes: one-arg ``jnp.where`` and
  ``jnp.nonzero``/``flatnonzero``/``argwhere``/``unique`` without
  ``size=`` — symbol ``<qual>:shape-trap:<fn>`` (these trap regardless
  of taint: the shape depends on VALUES).

Taint is deliberately conservative (near-zero false positives): sources
are parameters annotated ``jnp.ndarray``/``jax.Array`` and locals
assigned from jnp/lax expressions; it propagates through assignments in
source order but NOT through ``.shape``/``.ndim``/``.dtype``/``.size``
(shapes are static under jit — ``if x.shape[0] > 8:`` is legal trace
specialization, ``if x > 8:`` is the bug).
"""

from __future__ import annotations

import ast

from rtap_tpu.analysis.core import AnalysisContext, Finding
from rtap_tpu.analysis.kernels import dotted, functions_in, is_traced, \
    own_body_nodes

PASS_NAME = "trace-safety"
PARTITION = "file"
RULES = {
    "trace-safety": "data-dependent Python control flow, py-cast, "
                    "host call, or value-dependent output shape inside "
                    "traced ops/ code",
}

#: attribute hops that launder taint away: static under jit
_STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "itemsize"})

#: value-dependent-shape calls; where is special-cased (1-arg form only)
_SHAPE_TRAPS = ("nonzero", "flatnonzero", "argwhere", "unique")

_ARRAY_ANNOTATIONS = ("jnp.ndarray", "jax.Array", "jnp.array",
                      "jax.numpy.ndarray")


def _annotation_is_array(ann: ast.AST | None) -> bool:
    if ann is None:
        return False
    for node in ast.walk(ann):
        d = dotted(node) if isinstance(node, ast.Attribute) else None
        if d in _ARRAY_ANNOTATIONS:
            return True
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.value in _ARRAY_ANNOTATIONS:
            return True
    return False


def _tainted_names(expr: ast.AST, tainted: set[str],
                   skip_identity: bool = False) -> set[str]:
    """Tainted names read by expr, NOT reached through a static
    (.shape-style) attribute hop. ``skip_identity`` additionally skips
    ``is None``-style comparisons (for `if` tests: identity clauses are
    structural, `x.shape[0] > 2 and prev is not None` is legal)."""
    hits: set[str] = set()

    def rec(node):
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return  # static under jit: taint stops here
        if skip_identity and isinstance(node, ast.Compare) \
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in node.ops):
            return
        if isinstance(node, ast.Name) and node.id in tainted:
            hits.add(node.id)
        for child in ast.iter_child_nodes(node):
            rec(child)

    rec(expr)
    return hits


def _expr_traces(expr: ast.AST) -> bool:
    """Expr builds on jnp/lax (so its value is traced)."""
    for node in ast.walk(expr):
        d = None
        if isinstance(node, (ast.Attribute, ast.Name)):
            d = dotted(node)
        if d and d.split(".", 1)[0] in ("jnp", "lax"):
            return True
    return False


def _taint_fixpoint(fn: ast.FunctionDef) -> set[str]:
    """Names carrying traced values: array-annotated params plus every
    assignment target fed (transitively) by jnp/lax or a tainted name.
    Iterated to a fixed point so assignment ORDER inside loops cannot
    hide a flow (over-taints reads-before-binding — fine for a gate
    that wants zero false negatives on control flow)."""
    tainted: set[str] = {
        a.arg for a in fn.args.args + fn.args.kwonlyargs
        if _annotation_is_array(a.annotation)}
    assigns = [
        (st.targets if isinstance(st, ast.Assign) else [st.target],
         st.value)
        for st in own_body_nodes(fn)
        if isinstance(st, (ast.Assign, ast.AnnAssign, ast.AugAssign))
        and st.value is not None]
    changed = True
    while changed:
        changed = False
        for targets, value in assigns:
            if _expr_traces(value) or _tainted_names(value, tainted):
                for t in targets:
                    for n in _name_targets(t):
                        if n not in tainted:
                            tainted.add(n)
                            changed = True
    return tainted


def _name_targets(t: ast.AST):
    """BARE names a target binds — attribute/subscript targets are
    skipped (``self.state`` stores to an object, it does not create a
    local the taint set tracks; walking into it would falsely taint
    ``self``)."""
    if isinstance(t, ast.Name):
        yield t.id
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            yield from _name_targets(e)
    elif isinstance(t, ast.Starred):
        yield from _name_targets(t.value)




def run(ctx: AnalysisContext) -> list[Finding]:
    out: list[Finding] = []
    for sf in ctx.files_under("rtap_tpu/ops/"):
        if sf.tree is None:
            continue
        for qual, fn in functions_in(sf.tree):
            # top-level functions only: this codebase's kernels are
            # pure module-level functions; methods are host-boundary
            # wrappers (TpuStepRunner.step) whose float()/if ARE the
            # boundary, and nested closures trace inside their parent
            if "." in qual or not is_traced(fn):
                continue
            tainted = _taint_fixpoint(fn)
            for node in own_body_nodes(fn):
                # ---- if/while on traced values ----------------------
                if isinstance(node, (ast.If, ast.While)):
                    for var in sorted(_tainted_names(
                            node.test, tainted, skip_identity=True)):
                        out.append(Finding(
                            rule="trace-safety", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:if-on-traced:{var}",
                            message=f"Python `if` on traced value "
                                    f"`{var}` — under jit this is a "
                                    "concretization error (or silently "
                                    "bakes one branch in); use "
                                    "jnp.where / lax.cond"))
                    continue
                if isinstance(node, ast.Call):
                    d = dotted(node.func)
                    if isinstance(node.func, ast.Name) \
                            and node.func.id in ("bool", "int", "float") \
                            and any(_tainted_names(a, tainted)
                                    for a in node.args):
                        out.append(Finding(
                            rule="trace-safety", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:py-cast:{node.func.id}",
                            message=f"{node.func.id}() over a traced "
                                    "value — a host concretization "
                                    "under jit; keep the value on "
                                    "device (astype) or move the cast "
                                    "to the host boundary"))
                    elif isinstance(node.func, ast.Attribute) \
                            and node.func.attr == "tolist" \
                            and _tainted_names(node.func.value, tainted):
                        out.append(Finding(
                            rule="trace-safety", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:py-cast:tolist",
                            message=".tolist() over a traced value — "
                                    "a host fetch under jit"))
                    elif d and (d.startswith("np.")
                                or d.startswith("numpy.")) \
                            and any(_tainted_names(a, tainted)
                                    for a in node.args):
                        out.append(Finding(
                            rule="trace-safety", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:host-call:{d}",
                            message=f"{d}() fed a traced value — a "
                                    "host round-trip beyond the "
                                    "purity-fetch set; use the jnp "
                                    "equivalent"))
                    # ---- value-dependent output shapes --------------
                    if d == "jnp.where" and len(node.args) == 1:
                        out.append(Finding(
                            rule="trace-safety", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:shape-trap:where",
                            message="one-arg jnp.where returns a "
                                    "value-dependent shape — untraceable"
                                    "; use the three-arg form or "
                                    "jnp.nonzero(..., size=)"))
                    elif d and d.startswith("jnp.") \
                            and d.split(".")[-1] in _SHAPE_TRAPS \
                            and not any(kw.arg == "size"
                                        for kw in node.keywords):
                        out.append(Finding(
                            rule="trace-safety", path=sf.path,
                            line=node.lineno,
                            symbol=f"{qual}:shape-trap:"
                                   f"{d.split('.')[-1]}",
                            message=f"{d}() without size= returns a "
                                    "value-dependent shape — pass "
                                    "size= (with fill_value) to keep "
                                    "the program traceable"))
    return out
