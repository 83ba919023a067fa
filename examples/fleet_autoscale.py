"""Two-level autoscaling demo: instance policies riding an elastic node
fleet, with the bill in dollars.

Three views of the same workload:
  1. the discrete-event oracle with a NodeFleet (provision latency, warm
     pool, drain-before-terminate) and its cost report,
  2. the vectorized lax.scan simulator with the fleet in the scan carry,
  3. the vmapped sweep: a keepalive x warm-pool frontier in one compiled vmap.

    PYTHONPATH=src python examples/fleet_autoscale.py
"""

import time

from repro.core.cluster import Cluster
from repro.core.eventsim import EventSim, SimConfig
from repro.core.metrics import compute
from repro.core.policies import AsyncConcurrencyPolicy
from repro.core.simjax import JaxFleet, JaxPolicy, simulate_chunked
from repro.core.trace import TraceConfig, synthesize
from repro.fleet import (NodeFleet, NodeType, UtilizationFleetPolicy,
                         cost_from_sim)
from repro.fleet.sweep import sweep
from repro.opt.frontier import pareto_front

NODE = NodeType(name="worker-8", memory_mb=32_768.0, vcpus=8.0,
                price_per_hour=0.39, provision_s=60.0)


def main():
    trace = synthesize(TraceConfig(num_functions=120, duration_s=1800,
                                   target_total_rps=20, seed=42))
    print(f"trace: {len(trace):,} invocations / {trace.num_functions} functions")

    # -- 1. oracle with an elastic fleet -------------------------------------
    fleet = NodeFleet(UtilizationFleetPolicy(min_nodes=1, max_nodes=32,
                                             util_target=0.7, warm_frac=0.25),
                      node_type=NODE, cooldown_s=120.0)
    res = EventSim(trace, Cluster(1, node_memory_mb=NODE.memory_mb),
                   lambda f: AsyncConcurrencyPolicy(window_s=60, target=0.7),
                   SimConfig(), fleet=fleet).run()
    m = compute(res)
    bill = cost_from_sim(res, node_type=NODE)
    print(f"\noracle fleet: nodes_mean={m.nodes_mean:.1f} "
          f"provisions={m.node_provisions} terminations={m.node_terminations}")
    print(f"  slowdown_p99={m.slowdown_geomean_p99:.2f} "
          f"completed={m.completed} dropped={res.dropped}")
    print(f"  bill: ${bill.total_cost:.3f} (nodes ${bill.node_cost:.3f} "
          f"+ master ${bill.master_cost:.3f}) -> "
          f"${bill.cost_per_million:.2f}/1M requests "
          f"(churn ${bill.churn_cost:.3f}, idle ${bill.idle_cost:.3f})")

    # -- 2. vectorized simulator, fleet in the scan carry --------------------
    s = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.7),
                         fleet=JaxFleet(node_memory_mb=NODE.memory_mb,
                                        provision_s=NODE.provision_s,
                                        min_nodes=1, max_nodes=32,
                                        util_target=0.7, warm_frac=0.25,
                                        cooldown_s=120.0))
    print(f"\nsimjax fleet: nodes_mean={s['nodes_mean']:.1f} "
          f"slowdown_p99={s['slowdown_geomean_p99']:.2f} "
          f"(oracle/fluid node ratio "
          f"{m.nodes_mean / max(s['nodes_mean'], 1e-9):.2f})")

    # -- 3. vmapped trade-off frontier ---------------------------------------
    t0 = time.time()
    rows = sweep(trace, JaxPolicy(kind=0, keepalive_s=600),
                 JaxFleet(node_memory_mb=NODE.memory_mb,
                          provision_s=NODE.provision_s, min_nodes=1,
                          max_nodes=32, util_target=0.7, cooldown_s=120.0),
                 grid={"keepalive_s": [30.0, 120.0, 600.0, 1800.0],
                       "warm_frac": [0.0, 0.25, 0.5]},
                 node_type=NODE)
    dt = time.time() - t0
    print(f"\nsweep: {len(rows)} configs in {dt:.1f}s "
          f"({dt / len(rows) * 1e3:.0f} ms/config, one vmapped scan)")
    print(f"{'config':>24s} {'$/1M':>8s} {'p99 slow':>9s} {'nodes':>6s}")
    for r in pareto_front(rows):
        name = f"ka={r['keepalive_s']:.0f} warm={r['warm_frac']:.2f}"
        print(f"{name:>24s} {r['cost_per_million']:8.2f} "
              f"{r['slowdown_geomean_p99']:9.2f} {r['nodes_mean']:6.1f}")


if __name__ == "__main__":
    main()
