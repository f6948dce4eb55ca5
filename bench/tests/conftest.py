"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``.

They import the harness from ``bench/`` and the program from ``src/``.
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

import run  # noqa: E402


def shrink(cell, published_widths=False):
    """The cell at a size the CPU runs. A decode cell keeps its own shape of
    context (a seeded prefix or none) in a cache of 128 or 256 positions,
    with two layers of width 256 and a 512-token vocabulary; or, with
    `published_widths`, granite's widths, 8 layers and an 8,192-token
    vocabulary, where the random model depends on its context as the full
    one does (at width 256 it mostly copies its input token, and a cache
    never written goes unseen)."""
    long = cell.traffic["start_position"] > 0
    cell.traffic.update(cache_positions=256 if long else 128,
                        start_position=128 if long else 0)
    if published_widths:
        cell.config.update(num_hidden_layers=8, vocab_size=8192)
        cell.traffic["chunk_steps"] = 32
    else:
        cell.config.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=4,
                           num_key_value_heads=2, intermediate_size=512, vocab_size=512)
        cell.traffic["chunk_steps"] = 100 if long else 127
    return cell


@pytest.fixture
def tiny_cell():
    return lambda workload, **kw: shrink(run.load_cell(workload), **kw)
