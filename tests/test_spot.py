"""Spot-fleet subsystem: capacity tiers, the seeded hazard market, oracle
eviction mechanics, per-tier billing, zero-hazard regression (bit-for-bit),
oracle-vs-simjax spot parity, and the fig12 savings claim."""

import math

import pytest

from repro.core.runspec import RunSpec
from repro.core.cluster import GONE, Cluster
from repro.core.eventsim import EventSim, SimConfig
from repro.core.metrics import compute
from repro.core.policies import SpotAwarePolicy, SyncKeepalivePolicy
from repro.core.simjax import JaxFleet, JaxPolicy, simulate_chunked
from repro.core.trace import TraceConfig, synthesize
from repro.fleet import (NodeFleet, NodeType, PriceBook,
                         UtilizationFleetPolicy, cost_from_sim, cost_report)
from repro.fleet.spot import (SPOT_DEFAULT, CapacityTier, SpotMarket,
                              SpotNodeFleet, get_tier, list_tiers)

TC = TraceConfig(num_functions=60, duration_s=900, target_total_rps=10, seed=3)
NODE_MB = 8192.0
NT = NodeType(memory_mb=NODE_MB, provision_s=60.0, price_per_hour=1.0)


@pytest.fixture(scope="module")
def trace():
    return synthesize(TC)


def _policy(min_nodes=1, max_nodes=64):
    return UtilizationFleetPolicy(min_nodes=min_nodes, max_nodes=max_nodes,
                                  util_target=0.7, warm_frac=0.25)


def _spot_fleet(spot_fraction=0.6, hazard=8.0, notice=120.0, seed=0,
                **kw):
    tier = CapacityTier("spot", hazard_per_hour=hazard,
                        reclaim_notice_s=notice)
    return SpotNodeFleet(_policy(**kw), node_type=NT, cooldown_s=120.0,
                         spot_fraction=spot_fraction,
                         market=SpotMarket(tier, seed=seed))


def _run(trace, fleet, policy_factory=None):
    factory = policy_factory or (lambda f: SpotAwarePolicy(
        keepalive_s=600, spot_fraction=fleet.spot_fraction
        if isinstance(fleet, SpotNodeFleet) else 0.0,
        hazard_per_hour=fleet.market.tier.hazard_per_hour
        if isinstance(fleet, SpotNodeFleet) else 0.0))
    return EventSim(trace, Cluster(1, node_memory_mb=NODE_MB), factory,
                    SimConfig(), fleet=fleet).run()


# ---------------------------------------------------------------------------
# tier registry
# ---------------------------------------------------------------------------


def test_tier_registry_and_friendly_lookup():
    assert {"on_demand", "spot"} <= set(list_tiers())
    assert get_tier("on_demand").hazard_per_hour == 0.0
    assert get_tier("spot").price_multiplier < 1.0
    assert get_tier("spot").discount == pytest.approx(
        1.0 - SPOT_DEFAULT.price_multiplier)
    with pytest.raises(KeyError, match="registered"):
        get_tier("preemptible-gpu")


# ---------------------------------------------------------------------------
# seeded hazard sampler (determinism property)
# ---------------------------------------------------------------------------


def test_market_seeded_determinism_and_rate():
    tier = CapacityTier("t", hazard_per_hour=120.0, reclaim_notice_s=60.0)
    nodes = list(range(40))

    def schedule(seed):
        mkt = SpotMarket(tier, seed=seed)
        out = []
        for t in range(0, 600, 2):
            out.append(tuple(mkt.preempted(float(t), nodes)))
        return out

    assert schedule(7) == schedule(7)          # identical seed -> identical
    assert schedule(7) != schedule(8)          # schedule; seeds decorrelate
    # frequency matches the hazard: p = 1 - exp(-h * dt) per node per poll
    draws = sum(len(s) for s in schedule(7))
    polls = 299 * len(nodes)                   # first poll covers dt=0
    p = -math.expm1(-120.0 / 3600.0 * 2.0)
    assert draws / polls == pytest.approx(p, rel=0.25)


def test_market_first_poll_and_zero_hazard_draw_nothing():
    mkt = SpotMarket(CapacityTier("t", hazard_per_hour=1e6), seed=0)
    assert mkt.preempted(0.0, list(range(10))) == []      # dt=0 interval
    calm = SpotMarket(CapacityTier("c", hazard_per_hour=0.0), seed=0)
    calm.preempted(0.0, list(range(10)))
    assert calm.preempted(100.0, list(range(10))) == []


# ---------------------------------------------------------------------------
# oracle eviction mechanics
# ---------------------------------------------------------------------------


def test_oracle_spot_fleet_evicts_and_completes(trace):
    fleet = _spot_fleet(spot_fraction=0.6, hazard=20.0)
    cluster = Cluster(1, node_memory_mb=NODE_MB)
    res = EventSim(trace, cluster,
                   lambda f: SpotAwarePolicy(keepalive_s=600,
                                             spot_fraction=0.6,
                                             hazard_per_hour=20.0),
                   SimConfig(), fleet=fleet).run()
    m = compute(res)
    assert res.dropped == 0                    # storms queue, never drop
    assert m.node_evictions > 0
    assert res.spot_node_seconds > 0.0
    assert res.spot_node_seconds < res.node_seconds
    # only spot nodes are ever preempted, and preempted nodes stay gone
    reclaimed = [n for n in cluster.nodes if n.state == GONE]
    assert any(n.spot for n in reclaimed)
    assert m.completed > 0


def test_eviction_kills_warm_and_requeues_in_flight():
    """A short reclaim notice on a long-running function forces in-flight
    work to re-queue at the deadline (the storm's worst case)."""
    tc = TraceConfig(num_functions=4, duration_s=600, target_total_rps=2.0,
                     seed=5, dur_median_s=10.0, dur_sigma=0.1)
    trace = synthesize(tc)
    fleet = _spot_fleet(spot_fraction=1.0, hazard=60.0, notice=1.0)
    res = _run(trace, fleet,
               policy_factory=lambda f: SyncKeepalivePolicy(keepalive_s=600))
    assert compute(res).node_evictions > 0
    assert sum(r.requeued for r in res.records) > 0
    assert res.dropped == 0


def test_tier_split_tracks_spot_fraction(trace):
    fleet = _spot_fleet(spot_fraction=0.5, hazard=0.0)
    res = _run(trace, fleet)
    # a hazardless spot tier still bills its share: ~half the node-seconds
    share = res.spot_node_seconds / res.node_seconds
    assert 0.2 < share < 0.8
    assert compute(res).node_evictions == 0


# ---------------------------------------------------------------------------
# zero-hazard regression: spot machinery at zero == the plain fleet
# ---------------------------------------------------------------------------


def test_zero_spot_oracle_bit_for_bit(trace):
    plain = _run(trace, NodeFleet(_policy(), node_type=NT, cooldown_s=120.0),
                 policy_factory=lambda f: SyncKeepalivePolicy(keepalive_s=600))
    spot0 = _run(trace, _spot_fleet(spot_fraction=0.0, hazard=0.0),
                 policy_factory=lambda f: SyncKeepalivePolicy(keepalive_s=600))
    assert plain.creations == spot0.creations
    assert plain.teardowns == spot0.teardowns
    assert plain.node_seconds == spot0.node_seconds
    assert len(plain.records) == len(spot0.records)
    for a, b in zip(plain.records, spot0.records):
        assert a.start == b.start and a.end == b.end
    assert spot0.spot_node_seconds == 0.0 and spot0.node_evictions == 0


def test_zero_spot_simjax_bit_for_bit(trace):
    jf = JaxFleet(node_memory_mb=NODE_MB)
    sync = simulate_chunked(trace, JaxPolicy(family="sync",
                                             keepalive_s=600), fleet=jf)
    spot0 = simulate_chunked(
        trace, JaxPolicy(family="spot_aware", keepalive_s=600,
                         extra={"spot_fraction": 0.0,
                                "hazard_per_hour": 0.0}), fleet=jf)
    for k in sync:
        assert sync[k] == spot0[k], k
    assert spot0["spot_nodes_mean"] == 0.0


def test_simjax_hazard_causes_storm(trace):
    """The traced eviction flux produces the storm signature: more
    creations, worse tail, a billed spot share."""
    jf = JaxFleet(node_memory_mb=NODE_MB)
    base = simulate_chunked(
        trace, JaxPolicy(family="spot_aware", keepalive_s=600,
                         extra={"spot_fraction": 0.6,
                                "hazard_per_hour": 0.0}), fleet=jf)
    storm = simulate_chunked(
        trace, JaxPolicy(family="spot_aware", keepalive_s=600,
                         extra={"spot_fraction": 0.6,
                                "hazard_per_hour": 20.0}), fleet=jf)
    assert storm["creation_rate"] > base["creation_rate"]
    assert storm["slowdown_geomean_p99"] >= base["slowdown_geomean_p99"]
    assert storm["spot_nodes_mean"] > 0.0
    assert storm["spot_node_seconds"] < storm["node_seconds"]


# ---------------------------------------------------------------------------
# per-tier billing
# ---------------------------------------------------------------------------


def test_cost_report_bills_tiers_separately():
    full = cost_report(node_seconds=7200.0, spot_node_seconds=3600.0,
                       cpu_worker_overhead_s=0.0, cpu_master_overhead_s=0.0,
                       idle_node_share=0.0, completed=1_000_000,
                       node_type=NT, prices=PriceBook(spot_discount=0.65))
    # 1h on-demand at 1.0 + 1h spot at 0.35
    assert full.node_cost == pytest.approx(1.0 + 0.35)
    # the discount must NOT apply fleet-wide
    fleetwide = 2.0 * (1.0 - 0.65)
    assert full.node_cost != pytest.approx(fleetwide)
    # no spot seconds -> discount changes nothing
    od = cost_report(node_seconds=7200.0, cpu_worker_overhead_s=0.0,
                     cpu_master_overhead_s=0.0, idle_node_share=0.0,
                     completed=1, node_type=NT,
                     prices=PriceBook(spot_discount=0.65))
    assert od.node_cost == pytest.approx(2.0)


def test_cost_from_sim_uses_metered_spot_seconds(trace):
    res = _run(trace, _spot_fleet(spot_fraction=0.6, hazard=0.0))
    discounted = cost_from_sim(res, node_type=NT,
                               prices=PriceBook(spot_discount=0.65))
    od_priced = cost_from_sim(res, node_type=NT, prices=PriceBook())
    saved = od_priced.node_cost - discounted.node_cost
    expect = res.spot_node_seconds / 3600.0 * NT.price_per_hour * 0.65
    assert saved == pytest.approx(expect, rel=1e-6)


# ---------------------------------------------------------------------------
# oracle vs simjax spot parity (acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_spot_storm_parity_oracle_vs_simjax():
    """spot_storm at 0.25x: the fluid hazard/eviction flux holds the <=15%
    band on slowdown / memory / creation against the seed-AVERAGED oracle
    (the fluid is the hazard process's expectation, so parity is judged
    against the oracle's mean, not one Poisson realization)."""
    from repro.scenarios.runner import run_scenario
    sc = "spot_storm"
    fluid = run_scenario(sc, spec=RunSpec(engines=("simjax",),
                                          scale=0.25))[0]
    keys = ("slowdown_geomean_p99", "normalized_memory", "creation_rate")
    acc = {k: 0.0 for k in keys}
    seeds = (0, 1, 2)
    evictions = 0
    for seed in seeds:
        row = run_scenario(sc, sim=SimConfig(tick_s=1.0, seed=seed),
                           spec=RunSpec(engines=("eventsim",),
                                        scale=0.25))[0]
        evictions += row["node_evictions"]
        for k in keys:
            acc[k] += row[k] / len(seeds)
    assert evictions > 0                       # the storm actually storms
    for k in keys:
        gap = abs(acc[k] - fluid[k]) / abs(acc[k])
        assert gap <= 0.15, (k, gap, acc[k], fluid[k])


@pytest.mark.slow
def test_fig12_spot_beats_on_demand_oracle_confirmed():
    """Acceptance: the frontier finds a spot configuration strictly cheaper
    than the best all-on-demand point at equal-or-better p99, and the
    oracle confirms it (parity band + a strictly cheaper oracle bill)."""
    from benchmarks.fig12_spot_frontier import run
    rows, naive, winner, best_od, check = run()
    assert winner is not None
    assert winner["cost_per_million"] < best_od["cost_per_million"]
    assert winner["slowdown_geomean_p99"] <= best_od["slowdown_geomean_p99"]
    assert check["parity_ok"], check["gaps"]
    assert check["oracle_cheaper"], check


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------


def test_scenarios_cli_rejects_unknown_tier(capsys):
    from repro.launch.scenarios import main
    rc = main(["--scenario", "cold_tail", "--tier", "bogus-tier"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "unknown capacity tier" in err and "on_demand" in err


def test_cli_lists_include_spot(capsys):
    from repro.launch import frontier, scenarios
    assert scenarios.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "spot_storm" in out and "capacity tiers" in out
    assert frontier.main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "spot_storm" in out
    assert "spot_fraction" in out and "hazard_per_hour" in out
    assert "reclaim_notice_s" in out


# ---------------------------------------------------------------------------
# spot_aware family registration
# ---------------------------------------------------------------------------


def test_spot_aware_family_axes_and_space():
    from repro.core.policy_api import get_family
    from repro.opt.space import DEFAULT_SPACE, active_knobs, sweepable_knobs
    fam = get_family("spot_aware")
    assert {"keepalive_s", "cc", "spot_fraction",
            "hazard_per_hour"} == set(fam.axis_names())
    assert set(fam.sweepable_axes()) <= sweepable_knobs()
    assert "spot_fraction" in active_knobs("spot_aware")
    assert "spot_fraction" not in active_knobs("sync")
    assert "spot_fraction" in DEFAULT_SPACE.policy
    with pytest.raises(ValueError, match="bounds"):
        JaxPolicy(family="spot_aware", keepalive_s=600,
                  extra={"spot_fraction": 1.5, "hazard_per_hour": 0.0})


def test_spot_headroom_holds_extra_warm(trace):
    """Hazard-scaled headroom: the spot-aware policy holds more instances
    than plain sync under the same (hazardless) conditions when the
    declared hazard is large."""
    jf = JaxFleet(node_memory_mb=NODE_MB)
    lean = simulate_chunked(
        trace, JaxPolicy(family="spot_aware", keepalive_s=600,
                         extra={"spot_fraction": 0.0,
                                "hazard_per_hour": 0.0}), fleet=jf)
    padded = simulate_chunked(
        trace, JaxPolicy(family="spot_aware", keepalive_s=600,
                         extra={"spot_fraction": 1.0,
                                "hazard_per_hour": 60.0}), fleet=jf)
    assert padded["instances_mean"] > lean["instances_mean"]
