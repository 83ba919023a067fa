"""The comparison that decides ``correct``, at a tiny size on the CPU: the
program passes it, and the control (the reference computed in bfloat16,
put in the program's place) fails it."""

from __future__ import annotations

import pytest

from bench import check
from bench.tests.util import (RATES, assert_control_fails,
                              assert_program_run_correct, cells, tiny_cell)

#: every one-chip cell of BENCHMARK.json, so a new cell is tested here by
#: data alone, and the clustered fixture
CELLS = cells(chips=1) + [RATES]


@pytest.mark.parametrize("name", CELLS)
def test_program_run_is_correct(name):
    assert_program_run_correct(tiny_cell(name))


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    assert_control_fails(tiny_cell(name))


def test_checked_points_cover_both_halves():
    for seed in range(50):
        pts = check.checked_points(16, 4, seed)
        assert len(pts) == 4
        assert any(p < 8 for p in pts) and any(p >= 8 for p in pts)
    assert check.checked_points(1, 4, 0) == [0]
