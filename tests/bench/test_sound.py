"""The program as it is comes out ``correct`` at smoke size on the CPU,
through the same runners, comparison and limits as on the chip."""
from __future__ import annotations

import pytest

from smoke_cells import SERVE_CELL, run_smoke

CELLS = ["ring-train-deepseek7b-cais", SERVE_CELL["name"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(cell):
    line = run_smoke(cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
