"""Planet-scale machinery: the device-sharded chunked scan (1-device mesh
must be BIT-FOR-BIT the unsharded dispatch), long-tail function clustering
(exact for identical members, ≤1% on the planet trace), the fig9_planet
registration, and the unified CLI flag surface across all three launchers.

The multi-device tests skip on a 1-device host; CI's sharded-smoke job runs
this file under XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import numpy as np
import pytest

from repro.core.runspec import RunSpec
from repro.core.simjax import JaxFleet, JaxPolicy, simulate_chunked
from repro.core.trace import (FunctionProfile, RateTrace, TraceConfig,
                              synthesize, synthesize_rates)
from repro.opt import evaluate_points
from repro.scenarios import get_scenario, list_scenarios, run_scenario
from repro.scenarios.cluster import cluster_functions

# 61 functions: prime, so every device count > 1 forces the padded path
TC = TraceConfig(num_functions=61, duration_s=900, target_total_rps=8, seed=7)

FLOAT_KEYS = ("slowdown_geomean_p99", "normalized_memory", "creation_rate",
              "cpu_overhead", "instances_mean", "nodes_mean", "completed")
# the headline metrics the clustering approximation is allowed to move ≤1%
PARITY_KEYS = ("slowdown_geomean_p99", "normalized_memory", "creation_rate",
               "cpu_overhead")


def _ndev():
    import jax
    return len(jax.devices())


multi_device = pytest.mark.skipif(
    "len(__import__('jax').devices()) < 2",
    reason="needs >1 local device (set XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def trace():
    return synthesize(TC)


# ---------------------------------------------------------------------------
# sharded scan parity
# ---------------------------------------------------------------------------


def test_one_device_mesh_is_bitwise_identical(trace):
    pol = JaxPolicy(kind=0, keepalive_s=120)
    base = simulate_chunked(trace, pol, chunk_ticks=128, spec=RunSpec())
    shard = simulate_chunked(trace, pol, chunk_ticks=128,
                             spec=RunSpec(devices=1))
    for k in FLOAT_KEYS:
        assert base[k] == shard[k], k


def test_one_device_mesh_bitwise_with_rate_trace():
    rt = synthesize_rates(TC, tick_s=2.0)
    pol = JaxPolicy(kind=1, window_s=60, target=0.7)
    base = simulate_chunked(rt, pol, dt=2.0, chunk_ticks=128, spec=RunSpec())
    shard = simulate_chunked(rt, pol, dt=2.0, chunk_ticks=128,
                             spec=RunSpec(devices=1))
    for k in FLOAT_KEYS:
        assert base[k] == shard[k], k


@multi_device
def test_multi_device_mesh_matches_unsharded(trace):
    pol = JaxPolicy(kind=0, keepalive_s=120)
    base = simulate_chunked(trace, pol, chunk_ticks=128, spec=RunSpec())
    shard = simulate_chunked(trace, pol, chunk_ticks=128,
                             spec=RunSpec(devices=_ndev()))
    for k in PARITY_KEYS:
        # cross-device psum reassociates float32 sums; agreement is tight
        # but not bitwise
        assert base[k] == pytest.approx(shard[k], rel=1e-4), k


def test_point_axis_sharding_one_device(trace):
    jf = JaxFleet(node_memory_mb=8192.0)
    pts = [{"keepalive_s": float(ka)} for ka in (60.0, 300.0, 600.0)]
    base = evaluate_points(trace, JaxPolicy(kind=0), jf, pts)
    shard = evaluate_points(trace, JaxPolicy(kind=0), jf, pts, devices=1)
    for rb, rs in zip(base, shard):
        for k in PARITY_KEYS:
            assert rb[k] == rs[k], k


def test_point_axis_fallback_is_reported(trace, capsys):
    # 3 points cannot split over 2 devices: the batch runs on one, and says
    # so instead of quietly using fewer devices than asked for
    jf = JaxFleet(node_memory_mb=8192.0)
    pts = [{"keepalive_s": float(ka)} for ka in (60.0, 300.0, 600.0)]
    base = evaluate_points(trace, JaxPolicy(kind=0), jf, pts)
    assert "split over" not in capsys.readouterr().err
    shard = evaluate_points(trace, JaxPolicy(kind=0), jf, pts, devices=2)
    assert "3 point(s) split over 1 of the 2 requested" \
        in capsys.readouterr().err
    for rb, rs in zip(base, shard):
        for k in PARITY_KEYS:
            assert rb[k] == rs[k], k


def test_device_hint_names_xla_flags_only_on_cpu():
    from types import SimpleNamespace

    from repro.distributed.sharding import too_few_devices
    cpu = too_few_devices(4, [SimpleNamespace(platform="cpu")])
    assert "xla_force_host_platform_device_count=4" in cpu
    tpu = too_few_devices(4, [SimpleNamespace(platform="tpu")])
    assert tpu == "only 1 local device(s) visible"


@multi_device
def test_point_axis_sharding_multi_device(trace):
    jf = JaxFleet(node_memory_mb=8192.0)
    pts = [{"keepalive_s": float(ka)}
           for ka in (60.0, 120.0, 300.0, 600.0)]
    base = evaluate_points(trace, JaxPolicy(kind=0), jf, pts)
    shard = evaluate_points(trace, JaxPolicy(kind=0), jf, pts,
                            devices=_ndev())
    for rb, rs in zip(base, shard):
        for k in PARITY_KEYS:
            assert rb[k] == pytest.approx(rs[k], rel=1e-4), k


# ---------------------------------------------------------------------------
# function clustering
# ---------------------------------------------------------------------------


def _duplicated_rate_trace(k: int = 7, base_fns: int = 5,
                           seed: int = 3) -> RateTrace:
    """k identical copies of each of base_fns cold functions: the clustering
    exactness premise made literal."""
    rng = np.random.default_rng(seed)
    t_ticks = 300
    cols = rng.poisson(0.4, size=(t_ticks, base_fns)).astype(np.float32)
    counts = np.repeat(cols, k, axis=1)
    n = base_fns * k
    prof = FunctionProfile(
        rate=np.repeat(cols.mean(axis=0), k),
        dur_median=np.repeat(np.linspace(0.2, 1.5, base_fns), k),
        dur_sigma=np.full(n, 0.5),
        memory_mb=np.repeat(np.array([128.0, 256.0, 128.0, 512.0, 256.0]
                                     [:base_fns]), k),
        phase=np.zeros(n))
    return RateTrace(counts, 2.0, prof, float(t_ticks * 2.0))


def test_cluster_identical_members_is_exact():
    rt = _duplicated_rate_trace(k=7, base_fns=5)
    ct = cluster_functions(rt, below_rps=10.0)
    assert ct.num_functions == 5
    assert np.allclose(np.sort(ct.weights), [7.0] * 5)
    pol = JaxPolicy(kind=0, keepalive_s=120)
    full = simulate_chunked(rt, pol, dt=2.0, chunk_ticks=64, spec=RunSpec())
    clus = simulate_chunked(ct, pol, dt=2.0, chunk_ticks=64, spec=RunSpec())
    for k in PARITY_KEYS:
        # identical members evolve identically; only float reassociation
        # (weighted sum vs k-term sum) separates the two runs
        assert full[k] == pytest.approx(clus[k], rel=1e-5), k


def test_cluster_keeps_hot_functions_exact():
    rt = synthesize_rates(TC, tick_s=2.0)
    rates = np.asarray(rt.counts, np.float64).mean(axis=0) / rt.tick_s
    thr = float(np.median(rates))
    ct = cluster_functions(rt, below_rps=thr)
    assert ct.num_functions <= rt.num_functions
    # hot functions keep weight 1; total weight conserves the population
    assert np.isclose(ct.weights.sum(), rt.num_functions)
    assert (ct.weights >= 1.0 - 1e-9).all()


@pytest.mark.slow
def test_planet_clustered_parity_within_1pct():
    plain = run_scenario("fig9_planet",
                         spec=RunSpec(engines=("simjax",), scale=0.02))[0]
    clus = run_scenario("fig9_planet",
                        spec=RunSpec(engines=("simjax",), scale=0.02,
                                     cluster=1.0))[0]
    for k in PARITY_KEYS:
        rel = abs(plain[k] - clus[k]) / max(abs(plain[k]), 1e-9)
        assert rel <= 0.01, (k, rel)


# ---------------------------------------------------------------------------
# fig9_planet registration
# ---------------------------------------------------------------------------


def test_fig9_planet_registered():
    assert "fig9_planet" in list_scenarios()
    sc = get_scenario("fig9_planet")
    assert sc.rate_trace and not sc.oracle_ok
    assert sc.base.num_functions == 100_000
    rt = sc.build_trace(scale=0.01)
    assert isinstance(rt, RateTrace)
    assert rt.num_functions == 1000


def test_rate_scenarios_drop_oracle_even_forced():
    rows = run_scenario("fig9_planet",
                        spec=RunSpec(scale=0.01, force_oracle=True))
    assert [r["engine"] for r in rows] == ["simjax"]


# ---------------------------------------------------------------------------
# unified CLI flag surface
# ---------------------------------------------------------------------------

SHARED_FLAGS = ("--scale", "--billing", "--tier", "--devices", "--cluster")


def _parsers():
    from repro.launch import frontier, scenarios, trace as trace_cli
    return {"scenarios": scenarios.build_parser(),
            "frontier": frontier.build_parser(),
            "trace": trace_cli.build_parser()}


def test_all_launchers_accept_shared_flags():
    for name, ap in _parsers().items():
        opts = {s for a in ap._actions for s in a.option_strings}
        for flag in SHARED_FLAGS:
            assert flag in opts, (name, flag)


def test_shared_flag_defaults_match_runspec():
    spec = RunSpec()
    # the trace CLI takes a required positional scenario
    argv = {"scenarios": [], "frontier": [], "trace": ["cold_tail"]}
    for name, ap in _parsers().items():
        ns = ap.parse_args(argv[name])
        assert ns.billing is None and ns.tier is None, name
        assert ns.devices == spec.devices, name
        assert ns.cluster == spec.cluster, name


def test_validate_run_flags_exit2(capsys):
    import argparse
    from repro.launch.flags import validate_run_flags
    ns = argparse.Namespace(billing="bogus", tier=None, devices=0,
                            cluster=0.0)
    assert validate_run_flags(ns) == 2
    assert "unknown billing profile" in capsys.readouterr().err
    ns = argparse.Namespace(billing=None, tier="bogus", devices=0,
                            cluster=0.0)
    assert validate_run_flags(ns) == 2
    assert "unknown capacity tier" in capsys.readouterr().err
    ns = argparse.Namespace(billing=None, tier=None, devices=4096,
                            cluster=0.0)
    assert validate_run_flags(ns) == 2
    assert "host_platform_device_count" in capsys.readouterr().err
    ns = argparse.Namespace(billing=None, tier=None, devices=0,
                            cluster=-1.0)
    assert validate_run_flags(ns) == 2


def test_validate_run_flags_ok():
    import argparse
    from repro.fleet.spot import list_tiers
    from repro.launch.flags import validate_run_flags
    ns = argparse.Namespace(billing="aws_lambda", tier=list_tiers()[0],
                            devices=0, cluster=0.5)
    assert validate_run_flags(ns) == 0


def test_unknown_scenarios_exit2(capsys):
    from repro.launch.flags import unknown_scenarios
    assert unknown_scenarios(["cold_tail"]) == 0
    assert unknown_scenarios(["cold_tail", "nope"]) == 2
    assert "unknown scenario" in capsys.readouterr().err


def test_runspec_threads_devices_through_runner(trace):
    # run_scenario(devices=1) must agree bitwise with the unsharded run
    base = run_scenario("cold_tail",
                        spec=RunSpec(engines=("simjax",), scale=0.05))
    shard = run_scenario("cold_tail",
                         spec=RunSpec(engines=("simjax",), scale=0.05,
                                      devices=1))
    for k in PARITY_KEYS:
        assert base[0][k] == shard[0][k], k
