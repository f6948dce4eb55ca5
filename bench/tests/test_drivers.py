"""Every cell, rehearsed at a tiny size on the CPU through the functions the
chip runs use (run.run_cell: set-up, window, release, check)."""
import json

import pytest

from conftest import BENCH

import run

CELLS = [w["name"] for w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(tiny_cell, workload):
    cell = tiny_cell(workload)
    res = run.run_cell(cell, 2 ** 31 + 17, 1.0, False, None, None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks" and set(res["checks"]) == set(cell.limits)
    assert res["window_compiles"] >= 0 and res["setup_compiles"] > 0
