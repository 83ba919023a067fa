"""The comparison that decides ``correct``.

Every row a window job returns for a checked point is compared with the
reference's row for that point, metric by metric, as a relative gap
|program - reference| / |reference|.  Each metric's largest gap over the
checked rows of every window job is one number compared; its limit is in
``bench/limits/<cell>.json``.  A missing, extra or non-finite row reads as
an infinite gap.
"""

from __future__ import annotations

import math

import numpy as np

#: metric -> the short name its gap is printed under
CHECKS = {"slowdown_geomean_p99": "p99_gap",
          "normalized_memory": "memory_gap",
          "creation_rate": "creation_gap",
          "cpu_overhead": "cpu_gap",
          "total_cost": "cost_gap"}


def checked_points(n_points: int, k: int, seed: int) -> list[int]:
    """The point indices the reference checks: one drawn from the seed in
    each of ``k`` equal strata of the point list (all points when
    ``k >= n_points``), so a fault that spares half of the list is always
    seen."""
    if k >= n_points:
        return list(range(n_points))
    rng = np.random.default_rng(int(seed) % (1 << 63))
    strata = np.array_split(np.arange(n_points), k)
    return sorted(int(rng.choice(s)) for s in strata)


def rel_gap(value, ref) -> float:
    value, ref = float(value), float(ref)
    if not (math.isfinite(value) and math.isfinite(ref)):
        return math.inf
    if value == ref:
        return 0.0
    return abs(value - ref) / max(abs(ref), 1e-12)


def job_gaps(rows: list, ref_rows: list, points: list[int], n_points: int,
             keys) -> dict:
    """Largest relative gap per metric of one job's rows."""
    if len(rows) != n_points:
        return {k: math.inf for k in keys}
    gaps = {k: 0.0 for k in keys}
    for i, ref in zip(points, ref_rows):
        for k in keys:
            gaps[k] = max(gaps[k], rel_gap(rows[i][k], ref[k]))
    return gaps


def judge(gaps_per_job: list[dict], limits: dict) -> tuple[dict, int]:
    """(checks, failed jobs): each metric's largest gap beside its limit,
    and how many jobs had a gap over a limit."""
    keys = list(limits)
    worst = {k: max((g[k] for g in gaps_per_job), default=math.inf)
             for k in keys}
    failed = sum(any(not g[k] <= limits[k] for k in keys)
                 for g in gaps_per_job)
    checks = {CHECKS[k]: {"value": worst[k], "limit": limits[k]}
              for k in keys}
    return checks, failed
