"""The two gate commands CI and the docs call by name keep their contract:
one JSON line a surface on stdout, exit 0 where every bar holds, non-zero
where one is blown.

`python -m rtap_tpu.obs.selfbench` gates every instrument surface at <= 1 %
of the tick budget (docs/TELEMETRY.md); `scripts/ingest_bench.py --floor`
holds the binary ingest path over its CI floor (docs/INGEST.md). The blown
bar is forced through the module's own threshold — a budget no instrument
can meet, a floor no host can reach — so the verdict never rests on how
busy the test host is."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ingest_bench():
    spec = importlib.util.spec_from_file_location(
        "_ingest_bench_under_test", os.path.join(REPO, "scripts", "ingest_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("budget_frac, rc", [(None, 0), (1e-12, 1)],
                         ids=["bars_hold", "budget_blown"])
def test_selfbench_gate_exits_by_its_bars(budget_frac, rc, monkeypatch, capsys):
    import rtap_tpu.obs.selfbench as selfbench

    if budget_frac is not None:
        monkeypatch.setattr(selfbench, "GATE_BUDGET_FRAC", budget_frac)
    assert selfbench.main() == rc
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["metric"] for ln in lines] == [name for name, _ in selfbench.GATE_MEASURES]
    assert all(ln["pass_1pct_budget"] == (rc == 0) for ln in lines)
    assert all(ln["budget_frac"] == selfbench.GATE_BUDGET_FRAC for ln in lines)


@pytest.mark.parametrize("floor_rows, rc", [(1, 0), (10 ** 12, 1)],
                         ids=["floor_holds", "floor_blown"])
def test_ingest_floor_exits_by_its_floor(floor_rows, rc, monkeypatch, capsys):
    ib = _ingest_bench()
    # the drives at a size that takes a second, against a floor that does
    # not depend on the host: any rate clears 1 row/s, none clears 10^12
    monkeypatch.setattr(ib, "FLOOR_SIZE", (16_384, 4_096, 64))
    monkeypatch.setattr(ib, "FLOOR_ROWS_PER_SEC", floor_rows)
    monkeypatch.setattr(ib, "FLOOR_SPEEDUP", 0.0)
    assert ib.main(["--floor"]) == rc
    (line,) = capsys.readouterr().out.splitlines()
    res = json.loads(line)
    assert res["metric"] == "ingest_bench" and res["pass_floor"] == (rc == 0)
    assert res["floor_rows_per_sec"] == floor_rows
    assert min(res["jsonl_rows_per_sec"], res["binary_rows_per_sec"],
               res["shm_rows_per_sec"]) > 0
