"""Benchmark driver.

Default: every figure benchmark, printing ``name,us_per_call,derived`` CSV.

``--quick`` is the CI regression tier: fig8 through the frontier engine at
0.1x, the scenario suite at 0.1x (oracle legs included at that scale), the
per-scenario frontier hypervolumes, the fig12 spot-vs-on-demand cost
ratio (fluid-only, deterministic), and the fig13 billing-delta gate
(provider-vs-ideal frontier rank shift + billed oracle parity), the
fig14 multi-region cells gate (failover slowdown + the worst cells
oracle-vs-fluid gap), and the fig15 optimizer duel (worst evo-vs-grid
hypervolume ratio at equal evaluation budget), collected into a flat
{metric: value}
dict where EVERY metric is lower-is-better (wall seconds, p99 slowdown,
$/1M requests, memory ratio, cost ratio).
``--json`` writes it (BENCH_ci.json in CI); ``--baseline`` compares against
a checked-in reference and exits non-zero when any metric regresses more
than ``--tolerance`` (default 25%) — the bench-smoke CI gate.

  PYTHONPATH=src:. python benchmarks/run.py                      # full CSV
  PYTHONPATH=src:. python benchmarks/run.py --quick \\
      --json BENCH_ci.json --baseline benchmarks/baseline.json

``benchmarks/baseline.json`` provenance: deterministic metrics (p99 / cost
/ memory — fixed seeds) are checked in at their measured values; wall-clock
entries carry 3x headroom over the authoring machine, so with the 25% gate
tolerance a CI runner may be ~3.75x slower before the gate trips while a
lost-vmap-class regression (10x+) still fails.  To refresh: run --quick
--json, copy metric values verbatim, multiply *_wall_s by 3.
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import sys
import time

MODULES = [
    "benchmarks.fig2_queueing_cdf",
    "benchmarks.fig3_slowdown",
    "benchmarks.fig4_memory",
    "benchmarks.fig5_creation_rate",
    "benchmarks.fig6_cpu_overhead",
    "benchmarks.fig7_container_concurrency",
    "benchmarks.fig8_tradeoff",
    "benchmarks.fig9_large_scale",
    "benchmarks.fig9_planet",
    "benchmarks.fig10_fleet_cost",
    "benchmarks.fig11_learned_policy",
    "benchmarks.fig12_spot_frontier",
    "benchmarks.fig13_billing_delta",
    "benchmarks.fig14_region_failover",
    "benchmarks.fig15_optimizer",
    "benchmarks.scenario_suite",
    "benchmarks.table1_trends",
    "benchmarks.roofline",
]

QUICK_SCALE = 0.1

# hypervolume reference point on the (cost_per_million, p99 slowdown) plane
# for the quick tier's 0.1x coarse grids: generously above every scenario's
# observed front so the dominated area is well-defined and a frontier that
# retreats ANYWHERE shrinks it.  The gate metric is 1/hypervolume
# (lower-is-better, like every other gate metric).
HV_REF = (2000.0, 50.0)


def quick_hypervolume() -> dict:
    """Per-scenario frontier hypervolume over the DEFAULT_SPACE coarse grid
    (ROADMAP: multi-objective CI tracking — a point-wise metric gate misses
    a front that got strictly worse between its endpoints)."""
    from repro.core.runspec import RunSpec
    from repro.opt import DEFAULT_SPACE, evaluate_scenario, hypervolume
    from repro.scenarios import get_scenario, list_scenarios
    points = DEFAULT_SPACE.points()
    out = {}
    for name in list_scenarios():
        if get_scenario(name).rate_trace:
            continue   # the planet path has its own dedicated wall gate
        rows = evaluate_scenario(name, points,
                                 spec=RunSpec(scale=QUICK_SCALE))
        hv = hypervolume(rows, *HV_REF)
        # hypervolume's no-finite-rows sentinel is NaN (PR 7 convention);
        # the inverse gate metric must turn that into inf, not NaN, so the
        # baseline comparison fails loudly instead of comparing False
        out[f"frontier_hv_inv_{name}"] = (
            1.0 / hv if math.isfinite(hv) and hv > 0 else math.inf)
    return out


def run_quick() -> dict:
    """The regression-gate metric set: small, deterministic (fixed seeds)
    except the wall clocks, every value lower-is-better."""
    from benchmarks import fig8_tradeoff, scenario_suite
    metrics: dict[str, float] = {}

    rows, front, wall = fig8_tradeoff.run(scale=QUICK_SCALE)
    metrics["fig8_wall_s"] = round(wall, 3)
    metrics["fig8_best_p99"] = min(r["slowdown_geomean_p99"] for r in rows)
    metrics["fig8_best_mem"] = min(r["normalized_memory"] for r in rows)
    metrics["fig8_best_cost_per_million"] = min(r["cost_per_million"]
                                                for r in rows)

    t0 = time.time()
    suite = scenario_suite.run(scale=QUICK_SCALE)
    metrics["scenario_suite_wall_s"] = round(time.time() - t0, 3)
    for name, res in suite.items():
        for r in res["rows"]:
            if r["engine"] == "simjax":
                metrics[f"{name}_p99"] = r["slowdown_geomean_p99"]
                metrics[f"{name}_simjax_wall_s"] = r["wall_s"]

    t0 = time.time()
    metrics.update(quick_hypervolume())
    metrics["frontier_hv_wall_s"] = round(time.time() - t0, 3)

    # planet scale (fig9_planet, rate-based workload): gate the full
    # planet path — clustering plus the (un)sharded chunked dispatch — at
    # 0.25x (25k functions, ~12.5M invocations); a lost-jit-cache,
    # lost-sharding, or lost-clustering regression is a several-x movement
    # here.  Slowdown rides along as a determinism check.
    from benchmarks import fig9_planet
    row, wall = fig9_planet.run(scale=0.25)
    metrics["fig9_planet_wall_s"] = round(wall, 3)
    metrics["fig9_planet_quick_p99"] = row["slowdown_geomean_p99"]

    # spot frontier: the fluid (deterministic) winner-vs-on-demand cost
    # ratio must not regress — a rising ratio means the spot subsystem
    # stopped finding savings; the oracle-confirm legs run in the full
    # fig12 benchmark, not the gate (they are seeded but slow)
    from benchmarks import fig12_spot_frontier
    t0 = time.time()
    _, _, winner, best_od, _ = fig12_spot_frontier.run(
        scale=QUICK_SCALE / fig12_spot_frontier.EVAL_SCALE, confirm=False)
    metrics["fig12_wall_s"] = round(time.time() - t0, 3)
    metrics["fig12_spot_cost_ratio"] = (
        winner["cost_per_million"] / best_od["cost_per_million"]
        if winner is not None else math.inf)

    # billing delta (repro.fleet.billing): the provider profiles must keep
    # REORDERING the frontier (rank_delta_inv is 1/max rank shift — it
    # goes infinite, failing the non-finite check, if provider billing
    # collapses into ideal) and the billed oracle-vs-fluid parity legs
    # must stay inside their band (deterministic: fixed seeds)
    from benchmarks import fig13_billing_delta
    t0 = time.time()
    f13 = fig13_billing_delta.run(
        scale=QUICK_SCALE / fig13_billing_delta.EVAL_SCALE)
    metrics["fig13_wall_s"] = round(time.time() - t0, 3)
    metrics["fig13_billing_rank_delta"] = (
        1.0 / f13["rank_shift"] if f13["rank_shift"] > 0 else math.inf)
    metrics["fig13_billed_parity"] = f13["parity"]

    # multi-region cells (repro.cells): the three Fig. 14 scenarios
    # through BOTH engines at the 0.25 parity-calibration point (the
    # parity band does not hold below ~0.1x, see EXPERIMENTS.md) — gates
    # the failover scenario's fluid slowdown (deterministic: fixed
    # seed), the worst oracle-vs-fluid slowdown gap across the cells
    # family, and the wall clock; the cell-count frontier sweep runs in
    # the full benchmark only
    from benchmarks import fig14_region_failover
    t0 = time.time()
    f14 = fig14_region_failover.run(sweep=False)
    metrics["fig14_wall_s"] = round(time.time() - t0, 3)
    metrics["fig14_failover_p99"] = f14["p99"]
    metrics["fig14_cell_parity"] = f14["parity"]

    # optimizer duel (repro.opt.evo): hypervolume at the grid's own
    # evaluation budget, population search vs enumeration, on the three
    # fig15 scenarios (two sync + the structural-gene cells space).  The
    # gate metric is the WORST grid/evo ratio: <= 1 means evo matched or
    # beat the grid everywhere at equal spend, so a regression in seeding,
    # variation, or budget accounting shows up as the ratio rising above
    # its baseline (deterministic: fixed seed, fluid engine only)
    from benchmarks import fig15_optimizer
    t0 = time.time()
    f15 = fig15_optimizer.run(scale=QUICK_SCALE)
    metrics["fig15_wall_s"] = round(time.time() - t0, 3)
    metrics["fig15_hv_at_budget"] = f15["worst_ratio"]

    # attribution ledger (repro.obs): trace diurnal through BOTH engines at
    # the 0.25 parity-calibration point and gate on (a) attribution-sum
    # consistency — components must reconstruct the aggregate ratios
    # exactly, so the baseline is 0 and ANY inconsistency fails — and
    # (b) the worst component-level oracle-vs-fluid gap (deterministic:
    # fixed seeds, single scenario)
    from repro.core.runspec import RunSpec
    from repro.obs import (check_ledger, ledger_from_chunked,
                           ledger_from_eventsim, ledger_parity)
    from repro.scenarios import run_scenario
    t0 = time.time()
    detail: dict = {}
    run_scenario("diurnal", detail=detail,
                 spec=RunSpec(scale=0.25, telemetry=64))
    led_o = ledger_from_eventsim(detail["oracle_result"])
    led_f = ledger_from_chunked(detail["fluid_summary"])
    metrics["obs_wall_s"] = round(time.time() - t0, 3)
    metrics["obs_attribution_problems"] = float(
        len(check_ledger(led_o)) + len(check_ledger(led_f)))
    metrics["obs_component_gap"] = max(
        ledger_parity(led_o, led_f).values())
    return metrics


def compare(measured: dict, baseline: dict, tolerance: float) -> list[str]:
    """Every baseline metric must satisfy measured <= ref * (1+tolerance);
    a baseline key missing from the measurement is itself a failure (the
    gate must not silently narrow)."""
    failures = []
    for key, ref in baseline.items():
        got = measured.get(key)
        if got is None:
            failures.append(f"{key}: missing from measurement")
        elif not math.isfinite(got):
            # NaN compares False against everything — a NaN'd metric must
            # fail the gate, not slip through the > comparison
            failures.append(f"{key}: non-finite measurement {got}")
        elif got > ref * (1.0 + tolerance):
            failures.append(f"{key}: {got:.4g} > {ref:.4g} "
                            f"(+{(got / ref - 1) * 100:.0f}%, "
                            f"tolerance {tolerance * 100:.0f}%)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="figure benchmarks / CI gate")
    ap.add_argument("--quick", action="store_true",
                    help="regression tier: fig8 via the frontier engine at "
                         f"{QUICK_SCALE}x + scenario suite at {QUICK_SCALE}x")
    ap.add_argument("--json", default=None,
                    help="write the quick-tier metrics here")
    ap.add_argument("--baseline", default=None,
                    help="compare against this reference; exit 1 on regression")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed relative regression (default 0.25)")
    args = ap.parse_args(argv)

    if not args.quick:
        if args.baseline or args.json:
            # the gate must fail closed: a miswired invocation that forgot
            # --quick would otherwise "pass" without ever comparing
            ap.error("--json/--baseline require --quick")
        print("name,us_per_call,derived")
        for mod_name in MODULES:
            mod = importlib.import_module(mod_name)
            mod.run()
        return 0

    print("name,us_per_call,derived")
    metrics = run_quick()
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(metrics, fh, indent=2, sort_keys=True)
    if args.baseline:
        with open(args.baseline) as fh:
            baseline = json.load(fh)
        failures = compare(metrics, baseline, args.tolerance)
        for f in failures:
            print(f"BENCH REGRESSION: {f}", file=sys.stderr)
        if failures:
            return 1
        print(f"bench gate: {len(baseline)} metrics within "
              f"{args.tolerance * 100:.0f}% of baseline", file=sys.stderr)
    return 0


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
