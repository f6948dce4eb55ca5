"""A run with the timed path broken underneath comes out not correct: once
for each fault that the cell's driver plants (its ``FAULTS``: the state
returned unchanged, half of the batch left out, an answer altered where it
is produced; no cell spans chips, so none leaves out an exchange)."""
import json

import pytest

from conftest import BENCH

import run

CASES = [
    (w["name"], fault)
    for w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]
    for fault in run.driver_module(run.load_cell(w["name"])).FAULTS
]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_caught(tiny_cell, workload, fault):
    cell = tiny_cell(workload, published_widths=True)
    with run.driver_module(cell).FAULTS[fault]():
        res = run.run_cell(cell, 29, 0.5, False, None, None)
    assert not res["correct"], res["checks"]
