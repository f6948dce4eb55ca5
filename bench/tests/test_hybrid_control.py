"""The hybrid cell's control, the plain reference computed in float8 e4m3
(the precision below the bf16 that the configuration states) and put in
the program's place, comes out not correct, where the program itself comes
out correct. At a size a test run can hold: granite-4.0-h at its published
widths with 8 layers (Mamba2 at 0-4 and 6-7, attention at 5), an
8,192-token vocabulary and 32 served tokens on each of the 32 lanes."""
from conftest import shrink

import run


def test_hybrid_control_is_caught():
    cell = shrink(run.load_cell("decode-granite-h-32lanes"), published_widths=True)
    res = run.run_cell(cell, 41, 0.0, False, None, None, control=True)
    assert res["correct"], res["checks"]
    assert any(v > cell.limits[k] for k, v in res["control"].items()), res["control"]
