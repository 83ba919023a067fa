"""Two-level autoscaling: node-fleet lifecycle, cost model, oracle/simjax
parity, control-plane capacity capping, and the vmapped parameter sweep."""

import math

import numpy as np
import pytest

from repro.core.cluster import DRAINING, GONE, PROVISIONING, UP, Cluster
from repro.core.control_plane import ControlPlane, SimWorkerBackend
from repro.core.eventsim import EventSim, SimConfig
from repro.core.metrics import compute
from repro.core.policies import AsyncConcurrencyPolicy, SyncKeepalivePolicy
from repro.core.simjax import JaxFleet, JaxPolicy, simulate_chunked
from repro.core.trace import TraceConfig, synthesize
from repro.fleet import (FleetManager, NodeFleet, NodeType,
                         ScheduleFleetPolicy, ThresholdFleetPolicy,
                         UtilizationFleetPolicy, cost_from_sim, cost_report)
from repro.fleet.sweep import sweep
from repro.opt.frontier import pareto_front
from repro.opt.space import grid_points
from repro.serving.engine import ServeRequest

TC = TraceConfig(num_functions=60, duration_s=900, target_total_rps=10, seed=3)
NODE_MB = 8192.0
NT = NodeType(memory_mb=NODE_MB, provision_s=60.0, price_per_hour=1.0)


@pytest.fixture(scope="module")
def trace():
    return synthesize(TC)


def _fleet(min_nodes=1, max_nodes=64, util_target=0.7, warm_frac=0.25,
           cooldown_s=120.0):
    return NodeFleet(UtilizationFleetPolicy(min_nodes=min_nodes,
                                            max_nodes=max_nodes,
                                            util_target=util_target,
                                            warm_frac=warm_frac),
                     node_type=NT, cooldown_s=cooldown_s)


def _run(trace, policy_factory, fleet, initial_nodes=1):
    sim = EventSim(trace, Cluster(initial_nodes, node_memory_mb=NODE_MB),
                   policy_factory, SimConfig(), fleet=fleet)
    return sim.run()


# ---------------------------------------------------------------------------
# fleet policies
# ---------------------------------------------------------------------------


def test_warm_pool_sizing():
    p = UtilizationFleetPolicy(util_target=0.5, warm_frac=0.5, min_nodes=1,
                               max_nodes=100)
    # 10 nodes' worth of used memory at target 0.5 -> 20 needed, +50% warm
    assert p.desired(0.0, 10 * NODE_MB, NODE_MB, 5) == 30
    # warm pool never drops below one spare node when anything runs
    assert p.desired(0.0, 0.4 * NODE_MB, NODE_MB, 1) == 2
    # clamped at both ends
    assert p.desired(0.0, 0.0, NODE_MB, 0) == 1
    assert p.desired(0.0, 1000 * NODE_MB, NODE_MB, 5) == 100


def test_threshold_policy_cooldown_gates_repeat_fire():
    p = ThresholdFleetPolicy(high=0.8, low=0.3, change=2, cooldown_s=100,
                             min_nodes=1, max_nodes=10)
    assert p.desired(0.0, 9 * NODE_MB, NODE_MB, 10) == 10  # clamped, fired
    p2 = ThresholdFleetPolicy(high=0.8, low=0.3, change=2, cooldown_s=100,
                              min_nodes=1, max_nodes=20)
    assert p2.desired(0.0, 9 * NODE_MB, NODE_MB, 10) == 12
    # within cooldown: hold
    assert p2.desired(50.0, 9 * NODE_MB, NODE_MB, 12) == 12
    # after cooldown, low watermark scales down
    assert p2.desired(200.0, 1 * NODE_MB, NODE_MB, 12) == 10


def test_schedule_policy_piecewise_and_usage_floor():
    p = ScheduleFleetPolicy(entries=((0.0, 2), (600.0, 8), (1200.0, 3)),
                            min_nodes=1, max_nodes=16)
    assert p.desired(10.0, 0.0, NODE_MB, 2) == 2
    assert p.desired(700.0, 0.0, NODE_MB, 2) == 8
    assert p.desired(1500.0, 0.0, NODE_MB, 8) == 3
    # never below what usage occupies
    assert p.desired(1500.0, 6 * NODE_MB, NODE_MB, 8) == 6


# ---------------------------------------------------------------------------
# oracle: lifecycle behaviour
# ---------------------------------------------------------------------------


def test_fleet_scales_with_load_and_bills(trace):
    fleet = _fleet()
    res = _run(trace, lambda f: AsyncConcurrencyPolicy(window_s=60, target=0.7),
               fleet)
    m = compute(res)
    assert res.dropped == 0
    assert m.completed > 0
    assert m.node_provisions > 0            # grew beyond the single seed node
    assert m.nodes_mean > 1.0
    assert res.node_seconds > 0.0
    assert math.isclose(res.node_seconds,
                        res.node_samples.sum() * SimConfig().tick_s)


@pytest.mark.slow
def test_placement_failure_triggers_scale_up_not_drop(trace):
    # tiny max so the fleet saturates: requests must queue, never drop
    small = _fleet(max_nodes=2)
    res = _run(trace, lambda f: SyncKeepalivePolicy(keepalive_s=600), small)
    assert res.dropped == 0
    # same trace WITHOUT a fleet on the same tiny cluster drops creations
    static = EventSim(synthesize(TC), Cluster(2, node_memory_mb=NODE_MB),
                      lambda f: SyncKeepalivePolicy(keepalive_s=600),
                      SimConfig()).run()
    assert static.dropped > 0


def test_drain_before_terminate():
    """A draining node lets in-flight work finish before termination."""
    cluster = Cluster(2, node_memory_mb=NODE_MB)
    fleet = _fleet(cooldown_s=0.0)
    node = cluster.nodes[0]
    node.used_mb = 100.0                      # a busy instance lives here
    cluster.start_drain(node)
    assert node.state == DRAINING
    assert not node.fits(10.0)                # no new placements while draining
    assert fleet.maybe_reclaim(cluster) == [] # still occupied: not reclaimed
    assert node.state == DRAINING
    cluster.release(node, 100.0)              # in-flight work finishes
    assert fleet.maybe_reclaim(cluster) == [node]
    assert node.state == GONE and not node.alive
    assert fleet.terminations == 1


@pytest.mark.slow
def test_scale_down_is_cooldown_gated(trace):
    fast = _run(trace, lambda f: AsyncConcurrencyPolicy(window_s=30, target=0.7),
                _fleet(cooldown_s=10.0))
    slow = _run(trace, lambda f: AsyncConcurrencyPolicy(window_s=30, target=0.7),
                _fleet(cooldown_s=600.0))
    # a long cooldown holds surplus nodes longer -> more billed node-time
    assert slow.node_seconds >= fast.node_seconds
    assert slow.node_terminations <= fast.node_terminations


def test_fleet_events_preserve_request_completion(trace):
    res = _run(trace, lambda f: AsyncConcurrencyPolicy(window_s=60, target=0.7),
               _fleet(cooldown_s=60.0))
    m = compute(res)
    base = compute(EventSim(synthesize(TC), Cluster(8), lambda f:
                            AsyncConcurrencyPolicy(window_s=60, target=0.7),
                            SimConfig()).run())
    # elasticity must not lose requests vs the static-cluster run
    assert m.completed >= base.completed * 0.98
    assert np.isfinite(m.slowdown_geomean_p99)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_cost_report_components_add_up():
    r = cost_report(node_seconds=7200.0, cpu_worker_overhead_s=3600.0,
                    cpu_master_overhead_s=1800.0, idle_node_share=0.5,
                    completed=1_000_000, node_type=NT)
    assert r.node_hours == pytest.approx(2.0)
    assert r.node_cost == pytest.approx(2.0 * NT.price_per_hour)
    assert r.total_cost == pytest.approx(r.node_cost + r.master_cost)
    assert r.cost_per_million == pytest.approx(r.total_cost)
    assert 0.0 < r.churn_cost < r.node_cost
    assert r.idle_cost == pytest.approx(0.5 * r.node_cost)


def test_longer_keepalive_costs_more_dollars(trace):
    cheap = cost_from_sim(_run(trace, lambda f: SyncKeepalivePolicy(30), _fleet()),
                          node_type=NT)
    warm = cost_from_sim(_run(trace, lambda f: SyncKeepalivePolicy(900), _fleet()),
                         node_type=NT)
    # keeping warm holds more nodes -> a bigger bill (the paper's trade-off
    # in dollars), and more of that bill is idle-attributed
    assert warm.node_hours > cheap.node_hours
    assert warm.total_cost > cheap.total_cost
    assert warm.idle_cost > cheap.idle_cost


# ---------------------------------------------------------------------------
# oracle vs vectorized simulator parity (acceptance criterion)
# ---------------------------------------------------------------------------


def test_fleet_parity_oracle_vs_simjax(trace):
    """EventSim and the lax.scan simulator agree on node counts and $-cost
    within 15% when the fleet layer is enabled (async reconciler: identical
    policy math on both sides)."""
    fleet = _fleet()
    res = _run(trace, lambda f: AsyncConcurrencyPolicy(window_s=60, target=0.7),
               fleet)
    m = compute(res)
    oracle_cost = cost_from_sim(res, node_type=NT)

    jf = JaxFleet(node_memory_mb=NODE_MB, provision_s=NT.provision_s,
                  min_nodes=1, max_nodes=64, util_target=0.7, warm_frac=0.25,
                  cooldown_s=120.0)
    s = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.7),
                         fleet=jf)
    fluid_cost = cost_report(
        node_seconds=s["node_seconds"], cpu_worker_overhead_s=s["cpu_worker_s"],
        cpu_master_overhead_s=s["cpu_master_s"], idle_node_share=0.0,
        completed=int(s["completed"]), node_type=NT)

    assert m.nodes_mean == pytest.approx(s["nodes_mean"], rel=0.15)
    assert res.node_seconds == pytest.approx(s["node_seconds"], rel=0.15)
    assert oracle_cost.total_cost == pytest.approx(fluid_cost.total_cost, rel=0.15)
    assert oracle_cost.cost_per_million == pytest.approx(
        fluid_cost.cost_per_million, rel=0.15)


def test_simjax_fleet_capacity_caps_instances(trace):
    tight = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.7),
                             fleet=JaxFleet(node_memory_mb=NODE_MB,
                                            min_nodes=1, max_nodes=2))
    roomy = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.7),
                             fleet=JaxFleet(node_memory_mb=NODE_MB,
                                            min_nodes=1, max_nodes=64))
    assert tight["nodes_mean"] <= 2.0 + 1e-6
    assert roomy["nodes_mean"] > tight["nodes_mean"]
    # capacity starvation must surface as queueing delay, not lost load
    assert tight["slowdown_geomean_p99"] >= roomy["slowdown_geomean_p99"]


def test_simjax_warm_frac_adds_nodes(trace):
    lean = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.7),
                            fleet=JaxFleet(node_memory_mb=NODE_MB, warm_frac=0.0))
    padded = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.7),
                              fleet=JaxFleet(node_memory_mb=NODE_MB, warm_frac=1.0))
    assert padded["nodes_mean"] > lean["nodes_mean"]
    assert padded["node_seconds"] > lean["node_seconds"]


# ---------------------------------------------------------------------------
# vmapped parameter sweep
# ---------------------------------------------------------------------------


def test_sweep_grid_and_rows(trace):
    rows = sweep(trace, JaxPolicy(kind=0, keepalive_s=120),
                 JaxFleet(node_memory_mb=NODE_MB),
                 grid={"keepalive_s": [30.0, 600.0],
                       "warm_frac": [0.0, 0.5]},
                 node_type=NT)
    assert len(rows) == 4
    for r in rows:
        assert {"keepalive_s", "warm_frac", "nodes_mean",
                "cost_per_million", "slowdown_geomean_p99"} <= set(r)
        assert r["cost_per_million"] > 0
    by = {(r["keepalive_s"], r["warm_frac"]): r for r in rows}
    # a warm pool costs money; a long keepalive holds more instance memory
    assert by[(30.0, 0.5)]["nodes_mean"] > by[(30.0, 0.0)]["nodes_mean"]
    assert by[(600.0, 0.0)]["normalized_memory"] > by[(30.0, 0.0)]["normalized_memory"]


def test_sweep_matches_single_runs(trace):
    jf = JaxFleet(node_memory_mb=NODE_MB)
    rows = sweep(trace, JaxPolicy(kind=0, keepalive_s=120), jf,
                 grid={"keepalive_s": [60.0, 300.0]}, node_type=NT)
    for row in rows:
        single = simulate_chunked(
            trace, JaxPolicy(kind=0, keepalive_s=row["keepalive_s"]), fleet=jf)
        assert row["nodes_mean"] == pytest.approx(single["nodes_mean"], rel=1e-4)
        assert row["instances_mean"] == pytest.approx(
            single["instances_mean"], rel=1e-4)


def test_sweep_rejects_unknown_params(trace):
    with pytest.raises(ValueError):
        sweep(trace, JaxPolicy(kind=0), JaxFleet(), grid={"bogus": [1.0]})


def test_pareto_front_is_non_dominated():
    rows = [{"cost_per_million": c, "slowdown_geomean_p99": s}
            for c, s in [(1, 5), (2, 3), (3, 4), (4, 1), (5, 2)]]
    front = pareto_front(rows)
    assert [(r["cost_per_million"], r["slowdown_geomean_p99"]) for r in front] \
        == [(1, 5), (2, 3), (4, 1)]
    assert grid_points({"a": [1, 2], "b": [3]}) == [
        {"a": 1, "b": 3}, {"a": 2, "b": 3}]


# ---------------------------------------------------------------------------
# real control plane: FleetManager caps live instances
# ---------------------------------------------------------------------------


def test_fleet_manager_caps_and_scales_control_plane():
    backend = SimWorkerBackend(cold_start_s=0.2, default_service_s=2.0)
    fm = FleetManager(UtilizationFleetPolicy(min_nodes=1, max_nodes=4,
                                             util_target=0.7, warm_frac=0.0),
                      node_type=NodeType(provision_s=1.0),
                      instances_per_node=2, cooldown_s=30.0, initial_nodes=1)
    cp = ControlPlane(backend, lambda f: SyncKeepalivePolicy(keepalive_s=600),
                      num_functions=8, fleet=fm)
    # burst of 8 functions -> 8 creates wanted, capacity is 2 instances
    for fn in range(8):
        cp.submit(ServeRequest(rid=fn, fn=fn, prompt=[1], max_new_tokens=1,
                               arrival_t=0.0), 0.0)
    assert len(cp.instances) <= fm.capacity()
    assert cp.snapshot()["deferred_creates"] > 0
    # ticks advance the clock: fleet scales up, deferred creates land
    t = 0.0
    while len(cp.completed) < 8 and t < 60.0:
        t += 0.5
        cp.tick(t)
    assert len(cp.completed) == 8           # nothing dropped, all served
    assert fm.nodes_up > 1                  # placement pressure scaled nodes up
    assert fm.provisions > 0
    assert fm.node_seconds > 0.0
    snap = cp.snapshot()["fleet"]
    assert snap["capacity_instances"] == fm.nodes_up * 2


def test_fleet_manager_scales_down_after_cooldown():
    fm = FleetManager(UtilizationFleetPolicy(min_nodes=1, max_nodes=8,
                                             util_target=0.7, warm_frac=0.0),
                      node_type=NodeType(provision_s=0.5),
                      instances_per_node=2, cooldown_s=5.0, initial_nodes=6)
    fm.tick(0.0, live_instances=12)
    assert fm.nodes_total >= 6              # fully loaded: holds
    fm.tick(1.0, live_instances=0)          # load vanished
    down_to = fm.nodes_total
    assert down_to < 6
    fm.tick(2.0, live_instances=0)          # within cooldown: no further drop
    assert fm.nodes_total == down_to
    fm.tick(10.0, live_instances=0)         # cooldown elapsed
    assert fm.nodes_total <= down_to
    assert fm.nodes_total >= 1              # never below min_nodes
