"""Scenario engine: registry, trace transforms, the chunked simulator's
chunk-size invariance, and the oracle-vs-simjax parity acceptance band."""

import dataclasses

import numpy as np
import pytest

from repro.core.runspec import RunSpec
from repro.core.simjax import JaxFleet, JaxPolicy, simulate_chunked
from repro.core.trace import TraceConfig, merge_traces, synthesize
from repro.scenarios import (BurstInject, PolicySpec, RateScale, Scenario,
                             Splice, TenantMerge, TimeWarp, get_scenario,
                             list_scenarios, parity_report, run_scenario)

TC = TraceConfig(num_functions=50, duration_s=900, target_total_rps=8, seed=7)


@pytest.fixture(scope="module")
def trace():
    return synthesize(TC)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_catalogue():
    names = list_scenarios()
    assert len(names) >= 5
    assert {"diurnal", "flash_crowd", "cold_tail", "multi_tenant",
            "fig9_production", "fleet_cost_stress"} <= set(names)
    for n in names:
        sc = get_scenario(n)
        assert sc.description and sc.figure
    with pytest.raises(KeyError):
        get_scenario("not_a_scenario")


def test_fig9_scenario_is_production_scale():
    sc = get_scenario("fig9_production")
    assert sc.base.num_functions == 2000
    assert not sc.oracle_ok            # discrete replay infeasible at 1.0x


def test_scenario_scaling_preserves_shape():
    sc = get_scenario("diurnal")
    small = sc.build_trace(scale=0.1)
    assert small.num_functions == int(round(sc.base.num_functions * 0.1))
    assert small.duration_s == sc.base.duration_s * 0.1
    assert len(small) > 0


# ---------------------------------------------------------------------------
# transforms
# ---------------------------------------------------------------------------


def test_time_warp_preserves_load_and_order(trace):
    rng = np.random.default_rng(0)
    out = TimeWarp(period_frac=0.5, depth=0.8)(trace, TC, rng)
    assert len(out) == len(trace)                 # no invocations lost
    assert (np.diff(out.t) >= 0).all()            # still sorted
    assert out.t.min() >= 0 and out.t.max() <= trace.duration_s
    # intensity actually varies: quarter-window arrival counts spread out
    q = np.histogram(out.t, bins=8)[0]
    q0 = np.histogram(trace.t, bins=8)[0]
    assert q.std() > q0.std()


def test_rate_scale_up_and_down(trace):
    rng = np.random.default_rng(0)
    up = RateScale(2.0)(trace, TC, rng)
    down = RateScale(0.5)(trace, TC, rng)
    assert len(up) == pytest.approx(2 * len(trace), rel=0.05)
    assert len(down) == pytest.approx(0.5 * len(trace), rel=0.1)
    assert (np.diff(up.t) >= 0).all()


def test_burst_inject_adds_load_only_in_window(trace):
    rng = np.random.default_rng(0)
    tf = BurstInject(at_frac=0.5, width_frac=0.1, factor=4.0, top_k=5)
    out = tf(trace, TC, rng)
    t0, t1 = 0.5 * trace.duration_s, 0.6 * trace.duration_s
    inside = ((out.t >= t0) & (out.t < t1)).sum()
    inside_before = ((trace.t >= t0) & (trace.t < t1)).sum()
    outside = ((out.t < t0) | (out.t >= t1)).sum()
    outside_before = ((trace.t < t0) | (trace.t >= t1)).sum()
    assert inside > inside_before                 # burst added load
    assert outside == outside_before              # only in the window


def test_splice_keeps_head_replaces_tail(trace):
    rng = np.random.default_rng(0)
    out = Splice(at_frac=0.5)(trace, TC, rng)
    cut = 0.5 * trace.duration_s
    head, head0 = out.t[out.t < cut], trace.t[trace.t < cut]
    assert np.array_equal(head, head0)            # head untouched
    # tail is a different realization of the same population
    assert not np.array_equal(out.t[out.t >= cut], trace.t[trace.t >= cut])
    assert out.num_functions == trace.num_functions


def test_tenant_merge_rekeys_second_population(trace):
    rng = np.random.default_rng(0)
    out = TenantMerge(num_functions_frac=0.5, rps_frac=0.5)(trace, TC, rng)
    assert out.num_functions == trace.num_functions + TC.num_functions // 2
    assert out.fn.max() >= trace.num_functions    # tenant B ids shifted
    assert len(out) > len(trace)
    assert (np.diff(out.t) >= 0).all()


def test_merge_traces_interleaves():
    a, b = synthesize(TC), synthesize(dataclasses.replace(TC, seed=9))
    m = merge_traces(a, b)
    assert len(m) == len(a) + len(b)
    assert m.num_functions == a.num_functions + b.num_functions


# ---------------------------------------------------------------------------
# chunked scan: the chunk size is a memory knob, not a model one
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy,fleet", [
    (JaxPolicy(kind=0, keepalive_s=120), None),
    (JaxPolicy(kind=1, window_s=60, target=0.7), None),
    (JaxPolicy(kind=1, window_s=60, target=0.7),
     JaxFleet(node_memory_mb=8192.0, min_nodes=1, max_nodes=32)),
])
def test_chunk_size_leaves_the_row_unchanged(trace, policy, fleet):
    """Same step math, segmented time axis + in-carry summary stats: 257-tick
    chunks (an uneven, padded tail) give the row one chunk of the whole
    horizon gives, every float metric to float32 summation-order error."""
    whole = simulate_chunked(trace, policy, fleet=fleet,
                             chunk_ticks=int(trace.duration_s))
    chunk = simulate_chunked(trace, policy, fleet=fleet, chunk_ticks=257)
    assert chunk.keys() == whole.keys()
    for key, val in whole.items():
        if isinstance(val, float):
            assert chunk[key] == pytest.approx(val, rel=1e-5), key


def test_chunked_handles_uneven_tail_chunk(trace):
    a = simulate_chunked(trace, JaxPolicy(kind=0, keepalive_s=120),
                         chunk_ticks=900)
    b = simulate_chunked(trace, JaxPolicy(kind=0, keepalive_s=120),
                         chunk_ticks=128)       # 900 = 7*128 + 4 (padded)
    for key in ("normalized_memory", "creation_rate", "completed"):
        assert a[key] == pytest.approx(b[key], rel=1e-4), key


def test_chunked_production_scale_small():
    """A 1000-function slice of the Fig. 9 replay runs through the chunked
    scan in the fast tier (the full 2000-fn / 3.5M-invocation version is
    slow-marked below)."""
    sc = get_scenario("fig9_production")
    trace = sc.build_trace(scale=0.5)
    s = simulate_chunked(trace, sc.policy.to_jax(), num_nodes=sc.num_nodes,
                         chunk_ticks=sc.chunk_ticks)
    assert np.isfinite(s["slowdown_geomean_p99"])
    # metrics cover the post-warmup half of the run
    assert s["completed"] > 0.3 * len(trace)


@pytest.mark.slow
def test_chunked_production_scale_full():
    """Acceptance: the 2000-function / ~3.5M-invocation scenario completes
    via the chunked scan without materializing per-tick histories."""
    sc = get_scenario("fig9_production")
    trace = sc.build_trace()
    assert trace.num_functions == 2000
    assert len(trace) > 3_000_000
    s = simulate_chunked(trace, sc.policy.to_jax(), num_nodes=sc.num_nodes,
                         chunk_ticks=sc.chunk_ticks)
    assert np.isfinite(s["slowdown_geomean_p99"])
    assert s["normalized_memory"] > 1.0


# ---------------------------------------------------------------------------
# one Scenario spec -> both engines, with parity (acceptance criterion)
# ---------------------------------------------------------------------------

PARITY_SCENARIOS = ["diurnal", "flash_crowd", "cold_tail", "multi_tenant",
                    "fleet_cost_stress"]


@pytest.mark.parametrize("name", PARITY_SCENARIOS)
def test_scenario_parity_oracle_vs_simjax(name):
    """Each oracle-feasible scenario replays through BOTH engines from one
    spec with <= 15% relative gap on slowdown / normalized memory /
    creation rate (the hybrid-methodology acceptance band)."""
    rows = run_scenario(name, spec=RunSpec(scale=0.25))
    assert {r["engine"] for r in rows} == {"eventsim", "simjax"}
    gaps = parity_report(rows)
    for metric, gap in gaps.items():
        assert gap <= 0.15, (name, metric, gap, rows)


@pytest.mark.slow
def test_fig9_scenario_parity_at_reduced_scale():
    """The production replay's oracle leg only runs shrunk; slowdown and
    memory hold the 15% band there (creation rate is out-of-band for this
    strongly bursty trace under the Poisson-renewal expiry model — a
    documented limitation, see EXPERIMENTS.md)."""
    rows = run_scenario("fig9_production", spec=RunSpec(scale=0.25))
    assert {r["engine"] for r in rows} == {"eventsim", "simjax"}
    gaps = parity_report(rows)
    assert gaps["slowdown_geomean_p99"] <= 0.15
    assert gaps["normalized_memory"] <= 0.15


def test_fig9_oracle_skipped_at_full_scale():
    rows = run_scenario("fig9_production",
                        spec=RunSpec(engines=("eventsim",), scale=1.0))
    assert rows == []                  # infeasible leg skipped, not crashed


def test_policyspec_bridges_both_engines():
    sync, asyn = PolicySpec(kind="sync", keepalive_s=42), \
        PolicySpec(kind="async", window_s=30, target=0.5)
    assert sync.to_jax().kind == 0 and sync.to_jax().keepalive_s == 42
    assert asyn.to_jax().kind == 1 and asyn.to_jax().target == 0.5
    assert sync.factory()(0).keepalive(0.0) == 42
    assert asyn.factory()(0).window_s == 30
    with pytest.raises(ValueError):
        PolicySpec(kind="bogus").factory()


def test_runner_row_schema():
    rows = run_scenario("cold_tail",
                        spec=RunSpec(engines=("simjax",), scale=0.1))
    assert len(rows) == 1
    r = rows[0]
    assert {"scenario", "engine", "scale", "invocations", "wall_s",
            "slowdown_geomean_p99", "normalized_memory",
            "creation_rate"} <= set(r)
    assert r["engine"] == "simjax"
