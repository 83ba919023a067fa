"""The frozen generator: the same seed gives the same workload, and the
counts each cell states."""

from __future__ import annotations

import numpy as np
import pytest

from bench import gen, harness
from bench.tests.util import RATES, tiny_cell


@pytest.mark.parametrize("name", ["azure2k.replay", RATES])
def test_same_seed_same_workload(name):
    cfg = tiny_cell(name).config
    big = 2**31 + 12_345
    a, b, c = (gen.generate(cfg, s) for s in (big, big, big + 1))
    arrays = ("counts",) if cfg["trace"]["kind"] == "rates" \
        else ("t", "fn", "dur")
    for k in arrays:
        np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
    assert not np.array_equal(a.profile.rate, c.profile.rate)
    assert not getattr(a, arrays[0]).flags.writeable


def test_stated_counts():
    az = harness.load_cell("azure2k.replay").config
    sw = harness.load_cell("azure2k.sweep16")
    assert gen.simulated_ticks(az) == 4800
    assert gen.work_per_job(az, 1) == 9_600_000
    assert gen.work_per_job(sw.config, len(sw.traffic["points"])) \
        == 153_600_000
    assert len({tuple(sorted(p.items())) for p in sw.traffic["points"]}) \
        == 16


def test_tiny_workload_matches_its_settings():
    cfg = tiny_cell(RATES).config
    data = gen.generate(cfg, 5)
    assert data.counts.shape == (gen.simulated_ticks(cfg),
                                 cfg["trace"]["num_functions"])
    rate = data.counts.sum() / cfg["trace"]["duration_s"]
    assert rate == pytest.approx(cfg["trace"]["target_total_rps"], rel=0.1)
    ev = gen.generate(tiny_cell("azure2k.replay").config, 5)
    assert np.all(np.diff(ev.t) >= 0)
    assert ev.fn.max() < 60
