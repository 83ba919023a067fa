"""The comparison that decides ``correct``, at a tiny size on the CPU: the
program passes it, and the control (the reference computed in bfloat16,
put in the program's place) fails it."""

from __future__ import annotations

import pytest

from bench import check, gen, harness
from bench.tests.util import RATES, run_tiny, tiny_cell

CELLS = ["azure2k.replay", RATES, "azure2k.sweep16"]


@pytest.mark.parametrize("name", CELLS)
def test_program_run_is_correct(name):
    rc, res, err = run_tiny(tiny_cell(name))
    assert rc == 0
    assert res["correct"] is True, err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"fn_ticks_per_s", "setup_s"}
    assert err.strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    cell = tiny_cell(name)
    failed = 0
    for seed in (11, 12, 13):
        data = gen.generate(cell.config, seed)
        n = len(cell.traffic.get("points", [{}]))
        pts = check.checked_points(n, cell.traffic.get("check_points", n),
                                   seed)
        ref = harness.reference_rows(cell, data, pts)
        ctl = harness.reference_rows(cell, data, pts, dtype="bfloat16")
        rows = [None] * n
        for i, r in zip(pts, ctl):
            rows[i] = r
        gaps = check.job_gaps(rows, ref, pts, n, list(cell.limits))
        failed += check.judge([gaps], cell.limits)[1]
    assert failed == 3


def test_checked_points_cover_both_halves():
    for seed in range(50):
        pts = check.checked_points(16, 4, seed)
        assert len(pts) == 4
        assert any(p < 8 for p in pts) and any(p >= 8 for p in pts)
    assert check.checked_points(1, 4, 0) == [0]
