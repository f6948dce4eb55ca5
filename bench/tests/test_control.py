"""The control, the plain reference computed in float8 e4m3 (the precision
below the bf16 that the configuration states) and put in the program's
place, comes out not correct, where the program itself comes out correct.
At a size a test run can hold: granite at its published widths with 16
layers and an 8,192-token vocabulary, 100 served tokens a lane."""
import pytest

from conftest import shrink

import run


@pytest.mark.parametrize("workload", ["decode-granite-long", "decode-granite-short"])
def test_decode_control_is_caught(workload):
    cell = shrink(run.load_cell(workload), published_widths=True)
    # more depth and served tokens than the fault tests: the control's
    # readings grow with both
    cell.config["num_hidden_layers"] = 16
    cell.traffic["chunk_steps"] = 100
    res = run.run_cell(cell, 37, 0.0, False, None, None, control=True)
    assert res["correct"], res["checks"]
    assert any(v > cell.limits[k] for k, v in res["control"].items()), res["control"]
