"""rtap-lint v3 (ISSUE 14): device-kernel pass fixtures + --update-baseline.

Same discipline as test_analysis.py — every new pass gets a positive
(deliberately-bad snippet fails), a negative (idiomatic-good snippet
passes), and a suppressed fixture, all over in-memory SourceFiles with
synthetic paths. The armed-gate subprocess canaries live in
test_static_checks.py; this file proves the library semantics fast.
"""

import json
import os

import pytest

from rtap_tpu.analysis import run_analysis
from rtap_tpu.analysis.core import AnalysisContext, Baseline, SourceFile
from rtap_tpu.analysis.prints import MUST_BE_STRICT

pytestmark = pytest.mark.quick


def lint(path, code, rules=None, docs="", parity="", extra=(),
         baseline=None):
    files = [SourceFile(path, code)]
    files += [SourceFile(p, c) for p, c in extra]
    ctx = AnalysisContext(root="/__fixture__", files=files,
                          docs_text=docs, parity_text=parity)
    return run_analysis("/__fixture__", baseline=baseline or Baseline([]),
                        rules=set(rules) if rules is not None else None,
                        ctx=ctx)


def syms(report):
    return sorted(f.symbol for f in report.findings)


# ------------------------------------------------------- twin-parity --
_ORACLE = ("rtap_tpu/models/oracle/_fx.py",
           "def foo_step(state, sdr, cfg):\n    return state\n\n\n"
           "class BarOracle:\n    def compute(self):\n        pass\n")


def test_twin_parity_name_pair_and_parity_text():
    kernel = ("import jax.numpy as jnp\n\n\n"
              "def foo_step(state, sdr, cfg):\n    return jnp.sum(sdr)\n")
    r = lint("rtap_tpu/ops/_fx.py", kernel, ["twin-parity"],
             extra=(_ORACLE,), parity="exercises foo_step here")
    assert r.findings == [] and r.ok
    # deleting the parity test re-fails the gate (the parity tree is an
    # analyzer INPUT, which is the acceptance property)
    r2 = lint("rtap_tpu/ops/_fx.py", kernel, ["twin-parity"],
              extra=(_ORACLE,), parity="")
    assert syms(r2) == ["foo_step:untested"]


def test_twin_parity_untwinned_and_signature():
    orphan = ("import jax.numpy as jnp\n\n\n"
              "def lonely_kernel(x):\n    return jnp.sum(x)\n")
    r = lint("rtap_tpu/ops/_fx.py", orphan, ["twin-parity"],
             parity="lonely_kernel")
    assert syms(r) == ["lonely_kernel:untwinned"]
    # name-paired twin with a different positional arity
    skew = ("import jax.numpy as jnp\n\n\n"
            "def foo_step(state, sdr, extra, cfg):\n"
            "    return jnp.sum(sdr)\n")
    r2 = lint("rtap_tpu/ops/_fx.py", skew, ["twin-parity"],
              extra=(_ORACLE,), parity="foo_step")
    assert syms(r2) == ["foo_step:signature"]


def test_twin_parity_annotation_and_host_suffix():
    ann = ("import jax.numpy as jnp\n\n\n"
           "# rtap: twin[BarOracle] — stateful oracle\n"
           "def odd_kernel(state):\n    return jnp.sum(state)\n")
    r = lint("rtap_tpu/ops/_fx.py", ann, ["twin-parity"],
             extra=(_ORACLE,), parity="odd_kernel")
    assert r.findings == []
    # a dangling annotation target is an untwinned finding, not a pass
    dangling = ann.replace("BarOracle", "GhostOracle")
    r2 = lint("rtap_tpu/ops/_fx.py", dangling, ["twin-parity"],
              extra=(_ORACLE,), parity="odd_kernel")
    assert syms(r2) == ["odd_kernel:untwinned"]
    # same-file _host twin auto-pairs
    host = ("import jax.numpy as jnp\n\n\n"
            "def red_kernel(x):\n    return jnp.sum(x)\n\n\n"
            "def red_kernel_host(x):\n    return sum(x)\n")
    r3 = lint("rtap_tpu/ops/_fx.py", host, ["twin-parity"],
              parity="red_kernel")
    assert r3.findings == []


def test_twin_parity_scope_and_suppression():
    orphan = ("import jax.numpy as jnp\n\n\n"
              "def _private_kernel(x):\n    return jnp.sum(x)\n\n\n"
              "def dtype_helper(n):\n    return jnp.int16\n")
    # private kernels and dtype-table helpers are not the public surface
    r = lint("rtap_tpu/ops/_fx.py", orphan, ["twin-parity"])
    assert r.findings == []
    supp = ("import jax.numpy as jnp\n\n\n"
            "def infra_kernel(x):  # rtap: allow[twin-parity] — fixture\n"
            "    return jnp.sum(x)\n")
    r2 = lint("rtap_tpu/ops/_fx.py", supp, ["twin-parity"])
    assert r2.findings == [] and len(r2.suppressed) == 2  # both halves


# ------------------------------------------------------ trace-safety --
def test_trace_safety_if_on_traced_value():
    bad = ("import jax.numpy as jnp\n\n\n"
           "def k(x: jnp.ndarray):\n"
           "    y = jnp.sum(x)\n"
           "    if y > 0:\n        return y\n"
           "    return -y\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["trace-safety"])
    assert syms(r) == ["k:if-on-traced:y"]
    # static structure checks stay legal: shapes and is-None identity
    ok = ("import jax.numpy as jnp\n\n\n"
          "def k(x: jnp.ndarray, prev: jnp.ndarray | None):\n"
          "    if x.shape[0] > 2 and prev is not None:\n"
          "        return jnp.sum(x)\n"
          "    if prev is None:\n        return jnp.sum(x)\n"
          "    return x\n")
    assert lint("rtap_tpu/ops/_fx.py", ok, ["trace-safety"]).findings == []


def test_trace_safety_py_cast_and_host_call():
    bad = ("import jax.numpy as jnp\nimport numpy as np\n\n\n"
           "def k(x: jnp.ndarray):\n"
           "    total = float(jnp.sum(x))\n"
           "    return np.prod(x)\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["trace-safety"])
    assert "k:py-cast:float" in syms(r)
    assert "k:host-call:np.prod" in syms(r)
    # np over STATIC shape attributes is host-boundary-legal
    ok = ("import jax.numpy as jnp\nimport numpy as np\n\n\n"
          "def k(x: jnp.ndarray):\n"
          "    n = int(np.prod(x.shape))\n"
          "    return jnp.sum(x) / n\n")
    assert lint("rtap_tpu/ops/_fx.py", ok, ["trace-safety"]).findings == []


def test_trace_safety_shape_traps_and_suppression():
    bad = ("import jax.numpy as jnp\n\n\n"
           "def k(m: jnp.ndarray):\n"
           "    idx = jnp.where(m)\n"
           "    return jnp.nonzero(m)\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["trace-safety"])
    assert syms(r) == ["k:shape-trap:nonzero", "k:shape-trap:where"]
    ok = bad.replace("jnp.where(m)", "jnp.where(m, 1, 0)") \
            .replace("jnp.nonzero(m)", "jnp.nonzero(m, size=4)")
    assert lint("rtap_tpu/ops/_fx.py", ok, ["trace-safety"]).findings == []
    # a trailing allow covers its line (and the one below — core
    # grammar), so keep a spacer before the still-armed nonzero
    supp = ("import jax.numpy as jnp\n\n\n"
            "def k(m: jnp.ndarray):\n"
            "    idx = jnp.where(m)  # rtap: allow[trace-safety] — fixture\n"
            "    keep = m\n"
            "    return jnp.nonzero(m)\n")
    r2 = lint("rtap_tpu/ops/_fx.py", supp, ["trace-safety"])
    assert syms(r2) == ["k:shape-trap:nonzero"] and len(r2.suppressed) == 1


def test_trace_safety_out_of_scope():
    # methods are host-boundary wrappers; non-ops dirs are not kernels
    meth = ("import jax.numpy as jnp\n\n\n"
            "class Runner:\n"
            "    def step(self, x: jnp.ndarray):\n"
            "        y = jnp.sum(x)\n"
            "        if y > 0:\n            return float(y)\n"
            "        return 0.0\n")
    assert lint("rtap_tpu/ops/_fx.py", meth, ["trace-safety"]).findings == []
    bad = ("import jax.numpy as jnp\n\n\n"
           "def k(x: jnp.ndarray):\n"
           "    y = jnp.sum(x)\n"
           "    if y > 0:\n        return y\n"
           "    return -y\n")
    assert lint("rtap_tpu/service/_fx.py", bad,
                ["trace-safety"]).findings == []


# ------------------------------------------------------- donate-read --
_DONOR = ("from functools import partial\n\nimport jax\n\n\n"
          "@partial(jax.jit, donate_argnums=(0,))\n"
          "def burn(state, x):\n    return state, x\n\n\n")


def test_donate_read_positive_negative_suppressed():
    bad = _DONOR + ("def leak(state, x):\n"
                    "    s2, out = burn(state, x)\n"
                    "    return state, out\n")
    r = lint("rtap_tpu/service/_fx.py", bad, ["donate-read"])
    assert syms(r) == ["leak:state@burn"]
    # the idiomatic same-statement rebind never fires
    ok = _DONOR + ("def fine(state, x):\n"
                   "    state, out = burn(state, x)\n"
                   "    return state, out\n")
    assert lint("rtap_tpu/service/_fx.py", ok,
                ["donate-read"]).findings == []
    supp = bad.replace(
        "    return state, out\n",
        "    return state, out  # rtap: allow[donate-read] — fixture\n")
    r2 = lint("rtap_tpu/service/_fx.py", supp, ["donate-read"])
    assert r2.findings == [] and len(r2.suppressed) == 1


def test_donate_read_keyword_dotted_and_rebind():
    bad = _DONOR + ("class Loop:\n"
                    "    def tick(self, x):\n"
                    "        out = burn(state=self.state, x=x)\n"
                    "        return self.state\n")
    r = lint("rtap_tpu/service/_fx.py", bad, ["donate-read"])
    assert syms(r) == ["Loop.tick:self.state@burn"]
    ok = _DONOR + ("class Loop:\n"
                   "    def tick(self, x):\n"
                   "        self.state, out = burn(self.state, x)\n"
                   "        return self.state\n")
    assert lint("rtap_tpu/service/_fx.py", ok,
                ["donate-read"]).findings == []


def test_donate_read_lambda_params_are_fresh_scope():
    ok = _DONOR + ("def bench(state, time_fn):\n"
                   "    time_fn(lambda s: burn(s, 1))\n"
                   "    time_fn(lambda s: burn(s, 2))\n"
                   "    return state\n")
    assert lint("rtap_tpu/service/_fx.py", ok,
                ["donate-read"]).findings == []


def test_donate_read_nested_wrapper_is_file_local():
    factory = ("from functools import partial\n\nimport jax\n\n\n"
               "def make():\n"
               "    @partial(jax.jit, donate_argnums=(0,))\n"
               "    def run(state):\n        return state\n"
               "    return run\n")
    # another file calling something NAMED `run` must not match the
    # factory-local wrapper
    other = ("def drive(ctx):\n"
             "    out = run(ctx)\n"
             "    return ctx, out\n")
    r = lint("rtap_tpu/service/_fx.py", other, ["donate-read"],
             extra=(("rtap_tpu/ops/_factory.py", factory),))
    assert r.findings == []


# ------------------------------------------------------- static-hash --
def test_static_hash_unhashable_and_dangling():
    bad = ("from functools import partial\n\nimport jax\n\n\n"
           "@partial(jax.jit, static_argnames=(\"cfg\", \"gone\"))\n"
           "def f(state, cfg: dict):\n    return state\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["static-hash"])
    assert syms(r) == ["f:static:cfg", "f:static:gone"]
    ok = ("from functools import partial\n\nimport jax\n\n\n"
          "@partial(jax.jit, static_argnames=(\"cfg\",))\n"
          "def f(state, cfg: ModelConfig):\n    return state\n")
    assert lint("rtap_tpu/ops/_fx.py", ok, ["static-hash"]).findings == []
    oob = ("from functools import partial\n\nimport jax\n\n\n"
           "@partial(jax.jit, donate_argnums=(3,))\n"
           "def f(state, x):\n    return state\n")
    r2 = lint("rtap_tpu/ops/_fx.py", oob, ["static-hash"])
    assert syms(r2) == ["f:argnum:3"]


def test_jit_churn_loop_lambda_and_suppression():
    loop = ("import jax\n\n\n"
            "def churn(fns):\n"
            "    for fn in fns:\n"
            "        g = jax.jit(fn)\n"
            "    return g\n")
    r = lint("rtap_tpu/service/_fx.py", loop, ["jit-churn"])
    assert syms(r) == ["churn:jit-loop"]
    lam = ("import jax\n\n\n"
           "def build(cfg):\n"
           "    return jax.jit(lambda s: s)\n")
    r2 = lint("rtap_tpu/service/_fx.py", lam, ["jit-churn"])
    assert syms(r2) == ["build:jit-lambda"]
    hoisted = ("import jax\n\n\n"
               "def build(cfg):\n"
               "    def stepper(s):\n        return s\n"
               "    return jax.jit(stepper)\n")
    assert lint("rtap_tpu/service/_fx.py", hoisted,
                ["jit-churn"]).findings == []
    supp = loop.replace(
        "        g = jax.jit(fn)\n",
        "        g = jax.jit(fn)  # rtap: allow[jit-churn] — fixture\n")
    r3 = lint("rtap_tpu/service/_fx.py", supp, ["jit-churn"])
    assert r3.findings == [] and len(r3.suppressed) == 1


# ------------------------------------------------------ dtype-domain --
def test_dtype_domain_mix_and_widening_cast():
    bad = ("# rtap: domain[pa=u8, pb=u16]\n"
           "import jax.numpy as jnp\n\n\n"
           "def f(pa, pb):\n    return pa + pb\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["dtype-domain"])
    assert syms(r) == ["f:mix:u16~u8"]
    ok = bad.replace("pa + pb", "pa.astype(jnp.uint16) + pb")
    assert lint("rtap_tpu/ops/_fx.py", ok, ["dtype-domain"]).findings == []
    # state["<key>"] subscripts adopt declared domains too
    sub = ("# rtap: domain[perm=u16, qperm=u8]\n"
           "def f(state):\n"
           "    return state[\"perm\"] + state[\"qperm\"]\n")
    r2 = lint("rtap_tpu/ops/_fx.py", sub, ["dtype-domain"])
    assert syms(r2) == ["f:mix:u16~u8"]


def test_dtype_domain_i32_wrap_needs_clamp():
    bad = ("import jax.numpy as jnp\n\n\n"
           "def f(v, w):\n"
           "    cat = jnp.round(v).astype(jnp.int32)\n"
           "    return cat * w\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["dtype-domain"])
    assert syms(r) == ["f:i32-wrap:cat"]
    ok = bad.replace("jnp.round(v).astype(jnp.int32)",
                     "jnp.clip(jnp.round(v), -9, 9).astype(jnp.int32)")
    assert lint("rtap_tpu/ops/_fx.py", ok, ["dtype-domain"]).findings == []
    # the host's i64 widening is the wrap-safe idiom, not a key domain
    host = ("import numpy as np\n\n\n"
            "def f(v, w):\n"
            "    cat = np.round(v).astype(np.int64)\n"
            "    return cat * w\n")
    assert lint("rtap_tpu/models/oracle/_fx.py", host,
                ["dtype-domain"]).findings == []


def test_dtype_domain_undeclared_cast_and_suppression():
    bad = ("import jax.numpy as jnp\n\n\n"
           "def f(x):\n    return (x * 255.0).astype(jnp.uint8)\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["dtype-domain"])
    assert syms(r) == ["f:undeclared:u8"]
    declared = bad.replace(
        ".astype(jnp.uint8)",
        ".astype(jnp.uint8)  # rtap: domain[u8]")
    assert lint("rtap_tpu/ops/_fx.py", declared,
                ["dtype-domain"]).findings == []
    supp = bad.replace(
        ".astype(jnp.uint8)",
        ".astype(jnp.uint8)  # rtap: allow[dtype-domain] — fixture")
    r2 = lint("rtap_tpu/ops/_fx.py", supp, ["dtype-domain"])
    assert r2.findings == [] and len(r2.suppressed) == 1
    # unknown domain tokens are themselves findings
    junk = "# rtap: domain[pa=u12]\nx = 1\n"
    r3 = lint("rtap_tpu/ops/_fx.py", junk, ["dtype-domain"])
    assert syms(r3) == ["domain-syntax:pa"]


def test_dtype_domain_out_of_scope_dir():
    bad = ("# rtap: domain[pa=u8, pb=u16]\n"
           "def f(pa, pb):\n    return pa + pb\n")
    assert lint("rtap_tpu/obs/_fx.py", bad,
                ["dtype-domain"]).findings == []


# ----------------------------------------------------- wire-contract --
_WIRE_FIXTURE = (
    "import struct\n\n"
    "MAGIC = b\"XY1\"\n"
    "KIND_A = 1\n"
    "KIND_B = 2\n"
    "_KINDS = (KIND_A, KIND_B)\n"
    "HEADER = struct.Struct(\"<3sBH\")  # magic, kind, count\n")

_WIRE_DOCS = (
    "The XY1 frame:\n\n"
    "| offset | size | field | notes |\n"
    "|--------|------|-------|-------|\n"
    "| 0 | 3 | magic | `XY1` |\n"
    "| 3 | 1 | kind | 1=A, 2=B |\n"
    "| 4 | 2 | count | rows |\n")


def test_wire_contract_green_fixture():
    r = lint("rtap_tpu/ingest/_fx.py", _WIRE_FIXTURE, ["wire-contract"],
             docs=_WIRE_DOCS)
    assert r.findings == [] and r.ok


def test_wire_contract_struct_drift_fails():
    # widening count to u32 without touching the doc row = gate failure
    drifted = _WIRE_FIXTURE.replace('"<3sBH"', '"<3sBI"')
    r = lint("rtap_tpu/ingest/_fx.py", drifted, ["wire-contract"],
             docs=_WIRE_DOCS)
    assert syms(r) == ["HEADER.count"]


def test_wire_contract_doc_row_drift_fails():
    # mutating the documented layout row (the other direction) fails too
    r = lint("rtap_tpu/ingest/_fx.py", _WIRE_FIXTURE, ["wire-contract"],
             docs=_WIRE_DOCS.replace("| 4 | 2 | count |",
                                     "| 4 | 4 | count |"))
    assert syms(r) == ["HEADER.count"]
    # deleting the row entirely = undocumented field
    gone = _WIRE_DOCS.replace("| 4 | 2 | count | rows |\n", "")
    r2 = lint("rtap_tpu/ingest/_fx.py", _WIRE_FIXTURE, ["wire-contract"],
              docs=gone)
    assert syms(r2) == ["HEADER.count:undocumented"]


def test_wire_contract_type_codes():
    dup = _WIRE_FIXTURE.replace("KIND_B = 2", "KIND_B = 1")
    r = lint("rtap_tpu/ingest/_fx.py", dup, ["wire-contract"],
             docs=_WIRE_DOCS)
    assert "code:KIND_B" in syms(r)
    undoc = _WIRE_DOCS.replace("1=A, 2=B", "1=A")
    r2 = lint("rtap_tpu/ingest/_fx.py", _WIRE_FIXTURE, ["wire-contract"],
              docs=undoc)
    assert syms(r2) == ["code:KIND_B"]


def test_wire_contract_magic_collision_and_endian():
    twin = ("import struct\n\nMAGIC = b\"XY\"\n")
    r = lint("rtap_tpu/ingest/_fx.py", _WIRE_FIXTURE, ["wire-contract"],
             docs=_WIRE_DOCS,
             extra=(("rtap_tpu/resilience/_fx2.py", twin),))
    assert "magic:XY" in syms(r) or "magic:XY1" in syms(r)
    native = _WIRE_FIXTURE.replace('"<3sBH"', '"3sBH"')
    r2 = lint("rtap_tpu/ingest/_fx.py", native, ["wire-contract"],
              docs=_WIRE_DOCS)
    assert "fmt:HEADER:endian" in syms(r2)


def test_wire_contract_inline_width_line():
    code = ("import struct\n\n"
            "_MAGIC = b\"ZJ\"\n"
            "_HEADER = struct.Struct(\"<2sBI\")  # magic, typ, length\n")
    docs = 'framing: `b"ZJ" | typ u8 | length u32 | payload | crc32`\n'
    r = lint("rtap_tpu/resilience/_fx.py", code, ["wire-contract"],
             docs=docs)
    assert r.findings == []
    # doc narrows length to u16: drift
    r2 = lint("rtap_tpu/resilience/_fx.py", code, ["wire-contract"],
              docs=docs.replace("length u32", "length u16"))
    assert syms(r2) == ["_HEADER.length"]
    # no doc coverage at all: undocumented framing
    r3 = lint("rtap_tpu/resilience/_fx.py", code, ["wire-contract"],
              docs="")
    assert syms(r3) == ["_HEADER:undocumented"]


def test_wire_contract_comment_name_count_and_suppression():
    short = _WIRE_FIXTURE.replace("# magic, kind, count", "# magic, kind")
    r = lint("rtap_tpu/ingest/_fx.py", short, ["wire-contract"],
             docs=_WIRE_DOCS)
    assert syms(r) == ["fmt:HEADER:names"]
    supp = _WIRE_FIXTURE.replace(
        'HEADER = struct.Struct("<3sBH")',
        '# rtap: allow[wire-contract] — fixture\n'
        'HEADER = struct.Struct("<3sBH")')
    r2 = lint("rtap_tpu/ingest/_fx.py", supp, ["wire-contract"],
              docs=_WIRE_DOCS.replace("| 4 | 2 |", "| 4 | 4 |"))
    assert r2.findings == [] and len(r2.suppressed) == 1


# --------------------------------------------------- --update-baseline --
BAD_CODE = ("def f(p):\n    try:\n        load(p)\n"
            "    except Exception:\n        pass\n")


def _mini_repo(tmp_path, module="mod.py", code=BAD_CODE):
    """A throwaway tree run_analysis can discover: one violating serve
    module plus the strict-coverage pin stubs."""
    root = tmp_path / "repo"
    for stub in MUST_BE_STRICT:
        p = root / stub
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text("x = 1\n")
    (root / "rtap_tpu" / "service" / module).write_text(code)
    return str(root)


def _write_baseline(root, entries):
    path = os.path.join(root, "analysis_baseline.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"entries": entries}, fh)
    return path


def _read_entries(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)["entries"]


def test_update_baseline_rekeys_moved_path(tmp_path):
    from rtap_tpu.analysis.baseline_update import update_baseline

    root = _mini_repo(tmp_path, module="renamed.py")
    path = _write_baseline(root, [
        {"rule": "except-silent", "path": "rtap_tpu/service/old.py",
         "symbol": "f:except Exception", "why": "legacy swallow"}])
    summary = update_baseline(root, baseline_path=path)
    assert summary["unmatched"] == [] and summary["wrote"]
    assert summary["rekeyed"] == [(
        ("except-silent", "rtap_tpu/service/old.py",
         "f:except Exception"),
        ("except-silent", "rtap_tpu/service/renamed.py",
         "f:except Exception"))]
    ent = _read_entries(path)
    assert ent[0]["path"] == "rtap_tpu/service/renamed.py"
    assert ent[0]["why"] == "legacy swallow"  # preserved verbatim


def test_update_baseline_rekeys_moved_symbol(tmp_path):
    from rtap_tpu.analysis.baseline_update import update_baseline

    root = _mini_repo(tmp_path, code=BAD_CODE.replace("def f(", "def g("))
    path = _write_baseline(root, [
        {"rule": "except-silent", "path": "rtap_tpu/service/mod.py",
         "symbol": "f:except Exception", "why": "legacy swallow"}])
    summary = update_baseline(root, baseline_path=path)
    assert summary["unmatched"] == []
    ent = _read_entries(path)
    assert ent[0]["symbol"] == "g:except Exception"
    assert ent[0]["why"] == "legacy swallow"


def test_update_baseline_drops_stale_refuses_new(tmp_path):
    from rtap_tpu.analysis.baseline_update import update_baseline

    root = _mini_repo(tmp_path)
    path = _write_baseline(root, [
        # matches the real finding (kept)
        {"rule": "except-silent", "path": "rtap_tpu/service/mod.py",
         "symbol": "f:except Exception", "why": "legacy swallow"},
        # matches nothing on any axis (dropped)
        {"rule": "race", "path": "rtap_tpu/service/gone.py",
         "symbol": "C.n", "why": "obsolete"}])
    summary = update_baseline(root, baseline_path=path)
    assert summary["dropped"] == [
        ("race", "rtap_tpu/service/gone.py", "C.n")]
    assert [e["symbol"] for e in _read_entries(path)] == \
        ["f:except Exception"]
    # a NEW finding with no stale candidate is refused, never minted
    root2 = _mini_repo(tmp_path / "b")
    path2 = _write_baseline(root2, [])
    summary2 = update_baseline(root2, baseline_path=path2)
    assert summary2["unmatched"] == [
        ("except-silent", "rtap_tpu/service/mod.py",
         "f:except Exception")]
    assert not summary2["wrote"] and _read_entries(path2) == []


def test_update_baseline_leaves_whyless_for_a_human(tmp_path):
    from rtap_tpu.analysis.baseline_update import update_baseline

    root = _mini_repo(tmp_path)
    path = _write_baseline(root, [
        {"rule": "except-silent", "path": "rtap_tpu/service/mod.py",
         "symbol": "f:except Exception"}])  # no why
    summary = update_baseline(root, baseline_path=path)
    assert summary["format_errors"]
    # the malformed entry is neither fixed nor deleted — a human owns it
    ent = _read_entries(path)
    assert len(ent) == 1 and "why" not in ent[0]


# ------------------- review-hardening regressions (ISSUE 14 follow-ups) --
def test_donate_read_branches_are_mutually_exclusive():
    """A donation inside the if-body must not poison the else branch
    (they never both run), and code AFTER the If only sees bindings
    donated on EVERY branch (must-analysis)."""
    one_sided = _DONOR + (
        "def route(state, x, fast):\n"
        "    if fast:\n"
        "        state, out = burn(state, x)\n"
        "    else:\n"
        "        out = fallback(state)\n"
        "    return state, out\n")
    assert lint("rtap_tpu/service/_fx.py", one_sided,
                ["donate-read"]).findings == []
    both = _DONOR + (
        "def route(state, x, fast):\n"
        "    if fast:\n"
        "        s2, out = burn(state, x)\n"
        "    else:\n"
        "        s2, out = burn(state, x)\n"
        "    return state, out\n")
    r = lint("rtap_tpu/service/_fx.py", both, ["donate-read"])
    assert syms(r) == ["route:state@burn"]


def test_static_hash_checks_same_named_wrapper_in_second_file():
    """Two files defining a jit wrapper with the SAME bare name: the
    registry must check both (a by-name first-wins dict silently
    skipped the second one's broken spec)."""
    good = ("from functools import partial\n\nimport jax\n\n\n"
            "@partial(jax.jit, static_argnames=(\"cfg\",))\n"
            "def runner(state, cfg: ModelConfig):\n    return state\n")
    bad = ("from functools import partial\n\nimport jax\n\n\n"
           "@partial(jax.jit, static_argnames=(\"gone\",))\n"
           "def runner(state, cfg: ModelConfig):\n    return state\n")
    r = lint("rtap_tpu/ops/_fx_b.py", bad, ["static-hash"],
             extra=(("rtap_tpu/ops/_fx_a.py", good),))
    assert syms(r) == ["runner:static:gone"]


def test_donate_read_same_named_local_donor_wins():
    """When two files define donors with one name, a call site binds to
    the wrapper in ITS OWN file."""
    remote = ("from functools import partial\n\nimport jax\n\n\n"
              "@partial(jax.jit, donate_argnums=(1,))\n"
              "def burn2(aux, state):\n    return state\n")
    local = ("from functools import partial\n\nimport jax\n\n\n"
             "@partial(jax.jit, donate_argnums=(0,))\n"
             "def burn2(state, aux):\n    return state\n\n\n"
             "def use(state, aux):\n"
             "    out = burn2(state, aux)\n"
             "    return aux, out\n")
    # local donor donates position 0 (state); aux read stays legal
    r = lint("rtap_tpu/service/_fx.py", local, ["donate-read"],
             extra=(("rtap_tpu/ops/_fx_r.py", remote),))
    assert r.findings == []
    leak = local.replace("    return aux, out\n", "    return state\n")
    r2 = lint("rtap_tpu/service/_fx.py", leak, ["donate-read"],
              extra=(("rtap_tpu/ops/_fx_r.py", remote),))
    assert syms(r2) == ["use:state@burn2"]


def test_wire_contract_non_header_2s_struct_not_misclassified():
    """A struct that merely OPENS with a 2-byte string field is not the
    framing header — only a comment whose first field is `magic` (and
    the matching Ns) is checked against the framing docs."""
    code = ("import struct\n\n"
            "_MAGIC = b\"ZJ\"\n"
            "_HEADER = struct.Struct(\"<2sBI\")  # magic, typ, length\n"
            "_TRAILER = struct.Struct(\"<2sI\")  # pad, crc\n")
    docs = 'framing: `b"ZJ" | typ u8 | length u32 | payload | crc32`\n'
    r = lint("rtap_tpu/resilience/_fx.py", code, ["wire-contract"],
             docs=docs)
    assert r.findings == []


def test_update_baseline_never_transfers_why_to_unrelated_finding(tmp_path):
    """A stale entry whose (rule, path) matches a NEW, unrelated
    finding must not be re-keyed onto it (the why would grandfather a
    finding nobody reviewed): the tails differ, so the entry drops and
    the finding is refused."""
    from rtap_tpu.analysis.baseline_update import update_baseline

    root = _mini_repo(tmp_path)  # finding: f:except Exception
    path = _write_baseline(root, [
        {"rule": "except-silent", "path": "rtap_tpu/service/mod.py",
         "symbol": "g:except ValueError", "why": "old tolerance"}])
    summary = update_baseline(root, baseline_path=path)
    assert summary["rekeyed"] == []
    assert summary["dropped"] == [
        ("except-silent", "rtap_tpu/service/mod.py",
         "g:except ValueError")]
    assert summary["unmatched"] == [
        ("except-silent", "rtap_tpu/service/mod.py",
         "f:except Exception")]
    assert _read_entries(path) == []


def test_update_baseline_no_rekey_when_old_path_still_exists(tmp_path):
    """Round-1 (file-move) re-keys only when the entry's old file is
    GONE: if it still exists, a same-named finding in another file is
    more likely a new, unrelated site than a move — refuse, drop the
    stale entry, and leave the why out of the new finding."""
    from rtap_tpu.analysis.baseline_update import update_baseline

    root = _mini_repo(tmp_path, module="b.py")
    # the entry's path exists in the tree but carries no finding
    (  # noqa: the stub keeps a.py alive without violations
        __import__("pathlib").Path(root) / "rtap_tpu" / "service" / "a.py"
    ).write_text("x = 1\n")
    path = _write_baseline(root, [
        {"rule": "except-silent", "path": "rtap_tpu/service/a.py",
         "symbol": "f:except Exception", "why": "reviewed for a.py only"}])
    summary = update_baseline(root, baseline_path=path)
    assert summary["rekeyed"] == []
    assert summary["dropped"] == [
        ("except-silent", "rtap_tpu/service/a.py", "f:except Exception")]
    assert summary["unmatched"] == [
        ("except-silent", "rtap_tpu/service/b.py", "f:except Exception")]


def test_twin_parity_dangling_method_target_is_untwinned():
    """`# rtap: twin[Class.method]` must validate the FULL dotted
    target — a typoed method on a real class is a dangling pairing,
    not a pass."""
    ann = ("import jax.numpy as jnp\n\n\n"
           "# rtap: twin[BarOracle.no_such_method] — typo\n"
           "def odd_kernel(state):\n    return jnp.sum(state)\n")
    r = lint("rtap_tpu/ops/_fx.py", ann, ["twin-parity"],
             extra=(_ORACLE,), parity="odd_kernel")
    assert syms(r) == ["odd_kernel:untwinned"]
    good = ann.replace("BarOracle.no_such_method", "BarOracle.compute")
    r2 = lint("rtap_tpu/ops/_fx.py", good, ["twin-parity"],
              extra=(_ORACLE,), parity="odd_kernel")
    assert r2.findings == []


def test_dtype_domain_augassign_is_arithmetic_too():
    """`pa += pb` is the permanence-update idiom — the mix and wrap
    checks must see in-place updates, not just BinOp expressions."""
    bad = ("# rtap: domain[pa=u8, pb=u16]\n"
           "def f(pa, pb):\n"
           "    pa += pb\n"
           "    return pa\n")
    r = lint("rtap_tpu/ops/_fx.py", bad, ["dtype-domain"])
    assert syms(r) == ["f:mix:u16~u8"]
    wrap = ("import jax.numpy as jnp\n\n\n"
            "def f(v, w):\n"
            "    cat = jnp.round(v).astype(jnp.int32)\n"
            "    cat *= w\n"
            "    return cat\n")
    r2 = lint("rtap_tpu/ops/_fx.py", wrap, ["dtype-domain"])
    assert syms(r2) == ["f:i32-wrap:cat"]


def test_wire_contract_unrelated_comment_below_struct_is_not_a_field():
    """A plain comment on the next line must not be swallowed into the
    field list (continuations are only consumed while the list ends
    with a comma) — a prose edit near a framing must not go red."""
    prose = _WIRE_FIXTURE.replace(
        'HEADER = struct.Struct("<3sBH")  # magic, kind, count\n',
        'HEADER = struct.Struct("<3sBH")  # magic, kind, count\n'
        '# the walker helpers live below this line\n')
    r = lint("rtap_tpu/ingest/_fx.py", prose, ["wire-contract"],
             docs=_WIRE_DOCS)
    assert r.findings == []
    # the protocol.py idiom — trailing comma opens a continuation
    cont = _WIRE_FIXTURE.replace(
        'HEADER = struct.Struct("<3sBH")  # magic, kind, count\n',
        'HEADER = struct.Struct("<3sBH")  # magic, kind,\n'
        '# count\n')
    r2 = lint("rtap_tpu/ingest/_fx.py", cont, ["wire-contract"],
              docs=_WIRE_DOCS)
    assert r2.findings == []
