"""Spot-fleet demo: preemptible capacity tiers under an eviction hazard,
with the bill split per tier.

Three views of the same workload (mirroring examples/fleet_autoscale.py):
  1. the discrete-event oracle with a SpotNodeFleet — the market reclaims
     spot nodes with a 2-minute notice, warm instances are evicted, their
     in-flight work re-queues, and the bill discounts only spot node-hours,
  2. the vectorized lax.scan simulator with the spot hazard as a traced
     eviction flux (the spot_aware policy family's axes),
  3. the trade-off: sweep the spot purchase fraction and watch cost fall
     while eviction-driven cold-start storms push the p99 tail up.

    PYTHONPATH=src python examples/spot_fleet.py
"""

import time

from repro.core.cluster import Cluster
from repro.core.eventsim import EventSim, SimConfig
from repro.core.metrics import compute
from repro.core.policies import SpotAwarePolicy
from repro.core.simjax import JaxFleet, JaxPolicy, simulate_chunked
from repro.core.trace import TraceConfig, synthesize
from repro.fleet import (NodeType, PriceBook, SpotMarket, SpotNodeFleet,
                         UtilizationFleetPolicy, cost_from_sim, get_tier)

NODE = NodeType(name="worker-8", memory_mb=32_768.0, vcpus=8.0,
                price_per_hour=0.39, provision_s=60.0)
SPOT = get_tier("spot")                     # 0.35x price, hazard, 120s notice
PRICES = PriceBook(spot_discount=SPOT.discount)


def main():
    trace = synthesize(TraceConfig(num_functions=120, duration_s=1800,
                                   target_total_rps=20, seed=42))
    print(f"trace: {len(trace):,} invocations / {trace.num_functions} "
          f"functions; spot tier: {SPOT.price_multiplier:.2f}x on-demand, "
          f"{SPOT.hazard_per_hour:g} reclaims/node-hour, "
          f"{SPOT.reclaim_notice_s:g}s notice")

    # -- 1. oracle with a 60%-spot fleet -------------------------------------
    fleet = SpotNodeFleet(
        UtilizationFleetPolicy(min_nodes=1, max_nodes=32, util_target=0.7,
                               warm_frac=0.25),
        node_type=NODE, cooldown_s=120.0, spot_fraction=0.6,
        market=SpotMarket(SPOT, seed=0))
    res = EventSim(trace, Cluster(1, node_memory_mb=NODE.memory_mb),
                   lambda f: SpotAwarePolicy(
                       keepalive_s=600, spot_fraction=0.6,
                       hazard_per_hour=SPOT.hazard_per_hour),
                   SimConfig(), fleet=fleet).run()
    m = compute(res)
    bill = cost_from_sim(res, node_type=NODE, prices=PRICES)
    print(f"\noracle spot fleet: nodes_mean={m.nodes_mean:.1f} "
          f"evictions={m.node_evictions} "
          f"(spot share of node-hours "
          f"{m.spot_node_hours / max(m.node_hours, 1e-9):.0%})")
    print(f"  slowdown_p99={m.slowdown_geomean_p99:.2f} "
          f"completed={m.completed} requeued="
          f"{sum(r.requeued for r in res.records)}")
    print(f"  bill: ${bill.total_cost:.3f} -> "
          f"${bill.cost_per_million:.2f}/1M requests "
          f"(idle ${bill.idle_cost:.3f}, churn ${bill.churn_cost:.3f})")

    # -- 2. fluid twin: hazard as a traced eviction flux ---------------------
    jf = JaxFleet(node_memory_mb=NODE.memory_mb, provision_s=NODE.provision_s,
                  min_nodes=1, max_nodes=32, util_target=0.7, warm_frac=0.25,
                  cooldown_s=120.0, reclaim_notice_s=SPOT.reclaim_notice_s)
    s = simulate_chunked(trace, JaxPolicy(
        family="spot_aware", keepalive_s=600,
        extra={"spot_fraction": 0.6,
               "hazard_per_hour": SPOT.hazard_per_hour}), fleet=jf)
    print(f"\nsimjax spot fleet: nodes_mean={s['nodes_mean']:.1f} "
          f"(spot {s['spot_nodes_mean']:.1f}) "
          f"slowdown_p99={s['slowdown_geomean_p99']:.2f} "
          f"(oracle/fluid node ratio "
          f"{m.nodes_mean / max(s['nodes_mean'], 1e-9):.2f})")

    # -- 3. the spot fraction trade-off --------------------------------------
    print(f"\n{'spot_fraction':>14s} {'$/1M':>8s} {'p99 slow':>9s} "
          f"{'spot nodes':>10s}")
    t0 = time.time()
    for sf in (0.0, 0.3, 0.6, 0.9):
        s = simulate_chunked(trace, JaxPolicy(
            family="spot_aware", keepalive_s=600,
            extra={"spot_fraction": sf,
                   "hazard_per_hour": SPOT.hazard_per_hour}), fleet=jf)
        spot_s = s["spot_node_seconds"]
        od_rate = NODE.price_per_hour
        cost = ((s["node_seconds"] - spot_s) * od_rate
                + spot_s * od_rate * (1 - PRICES.spot_discount)) / 3600.0
        per_m = cost / max(s["completed"], 1) * 1e6
        print(f"{sf:14.1f} {per_m:8.2f} "
              f"{s['slowdown_geomean_p99']:9.2f} "
              f"{s['spot_nodes_mean']:10.1f}")
    print(f"({time.time() - t0:.1f}s; cheaper fleets, longer tails — "
          f"the frontier engine prices that trade, see "
          f"benchmarks/fig12_spot_frontier.py)")


if __name__ == "__main__":
    main()
