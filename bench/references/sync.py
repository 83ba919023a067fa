"""Plain reference of the sync keepalive family
(``repro.core.policy_api.SyncKeepaliveFamily``), written from its
published semantics and importing nothing of the program.

* the fluid tick of the sync-keepalive policy, on a static cluster or on
  an elastic node fleet, with the post-warm-up delay histogram and sums;
* the summary: the p99 slowdown geomean at the sync tail's finite-sample
  quantile, normalized memory, creation rate, CPU overhead and, on a
  fleet, the ideal-profile dollar cost.

It scans every tick in one loop (no chunks), vectorized over functions
and policy points.  The tick runs in float32 (NumPy's float32 arithmetic,
one rounding per operation, subnormals flushed as the chip does) or, for
the control, in bfloat16 (``bench.reference.rounder``).
"""

from __future__ import annotations

import numpy as np

from bench.reference import (F32, KA_GRID, QUANTILE, ftz, rounder,
                             slowdown_geomean)

ACC = ("instances", "mem_total", "mem_busy", "creations", "cpu_worker",
       "cpu_master", "useful", "nodes", "completions")


def finite_sample_quantile(n):
    """The sync tail's quantile for n requests, (q (n-1) + 1) / (n + 1):
    each arrival is independently warm or cold, so a function's empirical
    p99 over n requests sits above the population's."""
    return (QUANTILE * (n - 1.0) + 1.0) / (n + 1.0)


def _interp(tab, ka):
    """Per-function linear interpolation of a (F, K) table over KA_GRID at
    ka (P, F)."""
    g = KA_GRID.astype(F32)
    ka_c = np.clip(ka, g[0], g[-1])
    idx = np.clip(np.searchsorted(g, ka_c, side="right") - 1, 0, len(g) - 2)
    rows = np.arange(tab.shape[0])[None, :]
    e0, e1 = tab[rows, idx], tab[rows, idx + 1]
    return e0 + (ka_c - g[idx]) / (g[idx + 1] - g[idx]) * (e1 - e0)


def simulate(wl: dict, config: dict, points: list, fleet: bool,
             dtype: str = "float32", hist_block: int = 128) -> list:
    """Scan the whole horizon for each policy point (dicts with
    ``keepalive_s`` and, on a fleet, ``util_target``/``warm_frac``) and
    return one summary row per point."""
    q = rounder(dtype)
    dt = wl["tick_s"]
    counts, prof, weights = wl["counts"], wl["prof"], wl["weights"]
    n_ticks, f = counts.shape
    p = len(points)
    sim = config["sim"]
    nbins = config["nbins"]
    edges = np.logspace(-3, 5, nbins - 1, dtype=F32)
    warm_tick = int(n_ticks * config["warmup_frac"])

    def wsum(v):
        return (v if weights is None else v * weights).sum(axis=-1)

    counts = counts.astype(F32)
    dur = q(np.maximum(prof["dur_median"]
                       * np.exp(prof["dur_sigma"] ** 2 / 2),
                       dt * 0.25).astype(F32))
    mem = q((prof["memory_mb"] + sim["instance_overhead_mb"]).astype(F32))
    alive, tail = q(wl["alive"].astype(F32)), q(wl["tail"].astype(F32))
    weights = None if weights is None else weights.astype(F32)
    cold = max(1, int(round(sim["cold_start_s"] / dt)))
    ka = np.asarray([pt["keepalive_s"] for pt in points], F32)[:, None]

    inst, in_service, queue = (np.zeros((p, f), F32) for _ in range(3))
    starting = np.zeros((p, f, cold), F32)
    if fleet:
        fl = config["fleet"]
        prov = max(1, int(round(fl["provision_s"] / dt)))
        util_t = np.asarray([pt.get("util_target", fl["util_target"])
                             for pt in points], F32)
        warm_f = np.asarray([pt.get("warm_frac", fl["warm_frac"])
                             for pt in points], F32)
        min_n, max_n = fl["min_nodes"], fl["max_nodes"]
        node_mem = fl["node_memory_mb"]
        nodes = np.full(p, min_n, F32)
        pipe = np.zeros((p, prov), F32)
        cool = np.zeros(p, F32)
    static_nodes = float(config["num_nodes"])

    hist = np.zeros((p, f, nbins))
    warm_mass = np.zeros((p, f))
    arrtot = np.zeros((p, f))
    sums = np.zeros((p, len(ACC)))
    blk_idx, blk_w = [], []
    flat_base = (np.arange(p)[:, None] * f + np.arange(f)[None, :]) * nbins

    def flush():
        if blk_idx:
            hist.reshape(-1)[:] += np.bincount(
                np.concatenate(blk_idx), np.concatenate(blk_w),
                minlength=p * f * nbins)
            blk_idx.clear()
            blk_w.clear()

    for tick in range(n_ticks):
        arr = np.broadcast_to(counts[tick], (p, f))
        if fleet:
            nodes = q(nodes + pipe[:, 0])
            pipe = np.concatenate([pipe[:, 1:], np.zeros((p, 1), F32)],
                                  axis=1)
        inst = q(inst + starting[:, :, 0])
        starting = np.concatenate(
            [starting[:, :, 1:], np.zeros((p, f, 1), F32)], axis=2)
        turnover = q(np.minimum(q(in_service * dt / dur), in_service))
        free = q(np.maximum(q(inst - in_service), 0.0))
        dispatch = q(np.minimum(q(queue + arr), q(free + turnover)))
        arr_delayed = q(arr - np.maximum(q(dispatch - queue), 0.0))
        in_service = q(in_service + dispatch)
        queue = q(q(queue + arr) - dispatch)
        completions = ftz(q(np.minimum(q(in_service * dt / dur),
                                        in_service)))
        in_service = ftz(q(in_service - completions))
        served_avg = q(in_service + q(completions * np.minimum(dur / dt,
                                                               1.0)))
        busy_inst = np.minimum(inst, served_avg)
        idle_frac = q(np.maximum(q(inst - np.minimum(inst, in_service)), 0.0))
        idle = q(np.maximum(q(inst - np.minimum(inst, np.ceil(in_service))),
                            0.0))
        pending = q(starting.sum(axis=-1))
        if fleet:
            demand = q(np.maximum(q(queue - pending), 0.0))
        else:
            demand = q(np.maximum(q(arr - q(free + pending)), 0.0))

        # sync keepalive: renewal-exact expiry from the gap tables, read at
        # ka / instances (per-instance gaps thin with the instance count)
        inst1 = np.maximum(inst, 1.0)
        ka_arg = q(ka / inst1)
        e_alive = q(inst1 * q(_interp(alive, ka_arg)))
        p_tail = q(_interp(tail, ka_arg))
        rate = q(p_tail / np.maximum(e_alive, 1e-9))
        retire = q(idle_frac * q(-np.expm1(q(-rate * dt))))
        create = demand
        inst = ftz(q(inst - retire))

        if fleet:
            capacity = q(nodes * node_mem)
            committed = q(wsum(q(q(inst + q(starting.sum(axis=-1))) * mem)))
            free_mb = q(np.maximum(q(capacity - committed), 0.0))
            req_mb = q(wsum(q(create * mem)))
            scale = q(np.minimum(1.0, q(free_mb / np.maximum(req_mb, 1e-9))))
            create = q(create * scale[:, None])
            starting[:, :, cold - 1] = q(starting[:, :, cold - 1] + create)
            used = q(wsum(q(q(inst + q(starting.sum(axis=-1))) * mem)))
            pressure = q(np.maximum(q(req_mb * q(1.0 - scale)), 0.0))
            needed = np.ceil(q(q(q(used + pressure) / q(util_t * node_mem))
                               - 1e-9))
            warm = np.ceil(q(q(warm_f * np.maximum(needed, 1.0)) - 1e-9))
            desired = np.clip(needed + warm, min_n, max_n)
            have = q(nodes + pipe.sum(axis=-1))
            pipe[:, prov - 1] = q(pipe[:, prov - 1]
                                  + np.maximum(q(desired - have), 0.0))
            down_want = q(np.maximum(q(have - desired), 0.0))
            max_down = q(np.maximum(q(nodes - np.ceil(q(used / node_mem))),
                                    0.0))
            down = np.where(cool <= 0.0,
                            np.minimum(np.minimum(down_want, max_down),
                                       nodes), 0.0)
            nodes = q(nodes - down)
            cool = np.where(down > 0.0, np.ceil(fl["cooldown_s"] / dt),
                            np.maximum(cool - 1.0, 0.0))
            nodes_billed = q(nodes + pipe.sum(axis=-1))
        else:
            starting[:, :, cold - 1] = q(starting[:, :, cold - 1] + create)
            nodes_billed = np.full(p, static_nodes, F32)

        pending = q(starting.sum(axis=-1))
        future = q(inst + pending)
        drain = q(np.maximum(q(future / dur), 1e-6))
        cold_wait = np.where(pending > 0, cold * dt,
                             np.where(future < 0.5, 2.0 * cold * dt, 0.0))
        queue_pos = q(np.maximum(q(queue - q(0.5 * arr_delayed)), 0.0))
        delay = q(q(queue_pos / drain) + cold_wait)

        if tick < warm_tick:
            continue
        create_sum, teard = q(wsum(create)), q(wsum(retire))
        cpu_worker = (create_sum * sim["cpu_create_worker_s"]
                      + teard * sim["cpu_teardown_worker_s"]
                      + q(wsum(idle)) * sim["cpu_idle_per_s"] * dt
                      + sim["cpu_worker_floor_per_node_s"] * nodes_billed
                      * dt)
        cpu_master = (create_sum * sim["cpu_create_master_s"]
                      + teard * sim["cpu_teardown_master_s"]
                      + q(wsum(dispatch)) * sim["cpu_request_s"]
                      + sim["cpu_master_floor_per_s"] * dt)
        sums += np.stack([
            q(wsum(inst)), q(wsum(q(q(inst + pending) * mem))),
            q(wsum(q(busy_inst * mem))), create_sum, q(cpu_worker),
            q(cpu_master), q(wsum(q(completions * dur))), nodes_billed,
            q(wsum(completions))], axis=1)
        a_w = arr if weights is None else arr * weights
        d_w = arr_delayed if weights is None else arr_delayed * weights
        b = np.clip(np.searchsorted(edges, delay, side="right"), 0,
                    nbins - 1)
        blk_idx.append((flat_base + b).reshape(-1))
        blk_w.append(d_w.reshape(-1))
        warm_mass += a_w - d_w
        arrtot += a_w
        if len(blk_idx) >= hist_block:
            flush()
    flush()
    hist[:, :, 0] += warm_mass

    n = n_ticks - warm_tick
    rows = []
    for i in range(p):
        s = dict(zip(ACC, sums[i]))
        row = {
            "slowdown_geomean_p99": slowdown_geomean(
                hist[i], arrtot[i], prof["dur_median"], prof["dur_sigma"],
                sim["warm_latency_s"], wl["weights"],
                edges.astype(np.float64), finite_sample_quantile),
            "normalized_memory": s["mem_total"] / max(s["mem_busy"], 1e-9),
            "creation_rate": s["creations"] / (n * dt),
            "cpu_overhead": (s["cpu_worker"] + s["cpu_master"])
            / max(s["useful"], 1e-9),
        }
        if fleet:
            row["total_cost"] = (s["nodes"] * dt / 3600.0
                                 * config["node_price_per_hour"]
                                 + s["cpu_master"] / 3600.0
                                 * config["master_vcpu_per_hour"])
        rows.append(row)
    return rows
