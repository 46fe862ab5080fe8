"""The check's control, the program's bfloat16 exchange path, comes out
not correct on the tiny cells, on three seeds each."""

import pytest

from bench.control import control_runner
from bench.tests.tiny import CELL, PAGERANK, ROAD, run_tiny


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
@pytest.mark.parametrize("cell", [CELL, ROAD, PAGERANK])
def test_control_is_not_correct(monkeypatch, cell, seed):
    r = run_tiny(monkeypatch, cell, seed, runner=control_runner)
    assert not r["correct"]
