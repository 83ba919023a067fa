"""Plain reference of the fluid engine for the benchmark's cells.

A straightforward NumPy implementation of what the cells ask the
program to compute, written from the engine's published semantics and
importing nothing of it:

* the per-tick arrival matrix of an event stream;
* the gap tables the sync keepalive's renewal-exact expiry reads
  (empirical for event streams, the Poisson forms for count matrices);
* long-tail clustering into weighted rate-medoid super-functions;
* the fluid tick of the sync-keepalive policy, on a static cluster or on
  an elastic node fleet, with the post-warm-up delay histogram and sums;
* the summary: the p99 slowdown geomean of the delay x lognormal-duration
  mixture, normalized memory, creation rate, CPU overhead and, on a fleet,
  the ideal-profile dollar cost.

It scans every tick in one loop (no chunks), vectorized over functions and
policy points, and takes nothing the program made: it starts from the
benchmark's own workload arrays.

The tick runs in the configuration's stated precision, float32 (NumPy's
float32 arithmetic, one rounding per operation); the tables it reads are
built and the summary is solved in float64.  ``dtype="bfloat16"`` rounds
every intermediate of the tick to bfloat16 instead: that is the
benchmark's control, the reference put in the program's place at the
precision below the configuration's.
"""

from __future__ import annotations

import numpy as np

KA_GRID = np.geomspace(1e-3, 86_400.0, 56)
F32 = np.float32
TINY = np.finfo(F32).tiny
DUR_FLOOR, DUR_CAP = 0.02, 30.0
MIN_REQUESTS, COUNT_SLACK, QUANTILE = 5, 1e-3, 0.99
ACC = ("instances", "mem_total", "mem_busy", "creations", "cpu_worker",
       "cpu_master", "useful", "nodes", "completions")


# ---------------------------------------------------------------------------
# workload preparation
# ---------------------------------------------------------------------------


def event_counts(t, fn, duration_s: float, num_functions: int,
                 tick_s: float) -> np.ndarray:
    """(T, F) arrivals per tick of an event stream."""
    n_ticks = int(np.ceil(duration_s / tick_s))
    tick = np.minimum((t / tick_s).astype(np.int64), n_ticks - 1)
    flat = np.bincount(tick * num_functions + fn.astype(np.int64),
                       minlength=n_ticks * num_functions)
    return flat.reshape(n_ticks, num_functions).astype(np.float64)


def gap_tables_events(t, fn, num_functions: int):
    """Per function: E[min(gap, ka)] and P(gap > ka) over KA_GRID from the
    empirical inter-arrival gaps; a function with under two arrivals gets
    the idle-timer limits (alive = ka, tail = 1)."""
    k = len(KA_GRID)
    alive = np.tile(KA_GRID, (num_functions, 1))
    tail = np.ones((num_functions, k))
    order = np.argsort(fn, kind="stable")       # t is sorted: stays in time
    fs, ts = fn[order], t[order]
    bounds = np.searchsorted(fs, np.arange(num_functions + 1))
    for i in range(num_functions):
        g = np.sort(np.diff(ts[bounds[i]:bounds[i + 1]]))
        if len(g) == 0:
            continue
        csum = np.concatenate([[0.0], np.cumsum(g)])
        below = np.searchsorted(g, KA_GRID, side="right")
        alive[i] = (csum[below] + KA_GRID * (len(g) - below)) / len(g)
        tail[i] = (len(g) - below) / len(g)
    return alive, tail


def gap_tables_poisson(counts: np.ndarray, tick_s: float):
    """The same tables for Poisson arrivals at each function's mean rate."""
    lam = counts.mean(axis=0) / tick_s
    f = len(lam)
    alive = np.tile(KA_GRID, (f, 1))
    tail = np.ones((f, len(KA_GRID)))
    pos = lam > 0
    lg = lam[pos, None] * KA_GRID[None, :]
    alive[pos] = -np.expm1(-lg) / lam[pos, None]
    tail[pos] = np.exp(-lg)
    return alive, tail


def _mean(v) -> float:
    """Mean with the terms summed one by one in order."""
    return float(np.cumsum(v)[-1] / len(v))


def cluster(counts: np.ndarray, prof: dict, tick_s: float, below_rps: float,
            bins_per_octave: int):
    """Functions under ``below_rps`` grouped by quantized (log2 rate,
    log2 median duration, memory, sigma); each group runs as its
    rate-medoid member's arrival column, weighted by the member count, with
    the members' mean profile (geometric mean for the median duration).
    Returns (counts, profile, weights)."""
    rates = counts.mean(axis=0) / tick_s
    with np.errstate(divide="ignore"):
        lg_rate = np.round(np.log2(rates) * bins_per_octave)
    lg_dur = np.round(np.log2(np.maximum(prof["dur_median"], 1e-9))
                      * bins_per_octave)
    groups: dict = {}
    for i in range(len(rates)):
        if rates[i] < below_rps:
            key = ("cold", float(lg_rate[i]), float(lg_dur[i]),
                   float(prof["memory_mb"][i]),
                   round(float(prof["dur_sigma"][i]), 6))
        else:
            key = ("hot", i)
        groups.setdefault(key, []).append(i)
    members = list(groups.values())
    reps, weights = [], []
    out = {k: [] for k in ("rate", "dur_median", "dur_sigma", "memory_mb")}
    for idx in members:
        idx = np.asarray(idx)
        # the medoid is the first member (in function order) closest to
        # the members' mean rate, summed in member order: members with
        # equal counts tie, and a pair straddling the mean is split by the
        # mean's last bit
        reps.append(idx[np.argmin(np.abs(rates[idx] - _mean(rates[idx])))])
        weights.append(float(len(idx)))
        out["rate"].append(_mean(prof["rate"][idx]))
        out["dur_median"].append(np.exp(_mean(np.log(np.maximum(
            prof["dur_median"][idx], 1e-9)))))
        out["dur_sigma"].append(_mean(prof["dur_sigma"][idx]))
        out["memory_mb"].append(_mean(prof["memory_mb"][idx]))
    new_counts = counts[:, np.asarray(reps)].astype(np.float32)
    return (new_counts.astype(np.float64),
            {k: np.asarray(v, np.float64) for k, v in out.items()},
            np.asarray(weights))


def prepare(config: dict, data) -> dict:
    """Everything the scan reads, from a configuration and its workload."""
    tick = float(config["tick_s"])
    prof = {k: np.asarray(getattr(data.profile, k), np.float64)
            for k in ("rate", "dur_median", "dur_sigma", "memory_mb")}
    weights = None
    if hasattr(data, "counts"):
        counts = np.asarray(data.counts, np.float64)
        if data.tick_s != tick:
            raise ValueError("count matrix binned at another tick")
    else:
        counts = event_counts(data.t, data.fn, data.duration_s,
                              len(prof["rate"]), tick)
    if config.get("cluster_below_rps", 0.0) > 0:
        counts, prof, weights = cluster(
            counts, prof, tick, config["cluster_below_rps"],
            config["cluster_bins_per_octave"])
    if hasattr(data, "counts") or weights is not None:
        alive, tail = gap_tables_poisson(counts, tick)
    else:
        alive, tail = gap_tables_events(data.t, data.fn, len(prof["rate"]))
    return {"counts": counts, "prof": prof, "weights": weights,
            "alive": alive, "tail": tail, "tick_s": tick}


# ---------------------------------------------------------------------------
# the fluid scan
# ---------------------------------------------------------------------------


def _interp(tab, ka):
    """Per-function linear interpolation of a (F, K) table over KA_GRID at
    ka (P, F)."""
    g = KA_GRID.astype(F32)
    ka_c = np.clip(ka, g[0], g[-1])
    idx = np.clip(np.searchsorted(g, ka_c, side="right") - 1, 0, len(g) - 2)
    rows = np.arange(tab.shape[0])[None, :]
    e0, e1 = tab[rows, idx], tab[rows, idx + 1]
    return e0 + (ka_c - g[idx]) / (g[idx + 1] - g[idx]) * (e1 - e0)


def _ftz(x):
    """Flush float32 subnormals to zero, as the chip's float32 (and XLA's
    CPU backend) does: the integral idle count takes the ceiling of a
    decaying in-service mass, which differs between a subnormal and 0."""
    return np.where(np.abs(x) < TINY, F32(0.0), x)


def _rounder(dtype: str):
    if dtype == "float32":
        return lambda x: x
    if dtype != "bfloat16":
        raise ValueError(f"unknown precision {dtype!r}")
    import ml_dtypes
    return lambda x: np.asarray(x, np.float32).astype(
        ml_dtypes.bfloat16).astype(np.float32)


def simulate(wl: dict, config: dict, points: list, fleet: bool,
             dtype: str = "float32", hist_block: int = 128) -> list:
    """Scan the whole horizon for each policy point (dicts with
    ``keepalive_s`` and, on a fleet, ``util_target``/``warm_frac``) and
    return one summary row per point."""
    q = _rounder(dtype)
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
        completions = _ftz(q(np.minimum(q(in_service * dt / dur),
                                        in_service)))
        in_service = _ftz(q(in_service - completions))
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
        inst = _ftz(q(inst - retire))

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
                edges.astype(np.float64)),
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


# ---------------------------------------------------------------------------
# the summary solve
# ---------------------------------------------------------------------------


def _phi(z):
    """Standard normal CDF by Abramowitz-Stegun 7.1.26, the estimator's
    stated approximation (|error| < 1.5e-7)."""
    t = 1.0 / (1.0 + 0.3275911 * np.abs(z) / np.sqrt(2.0))
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    return 0.5 * (1.0 + np.sign(z) * (1.0 - poly * np.exp(-0.5 * z * z)))


def slowdown_geomean(hist, arrtot, dur_median, dur_sigma, warm, weights,
                     edges, block: int = 4096) -> float:
    """Geomean over functions with at least five requests of the 99th
    percentile of S = 1 + (W + warm) / D, W from the function's delay
    histogram and D its clipped lognormal duration, at the finite-sample
    quantile (q (n-1) + 1) / (n + 1); solved by 60 bisection steps."""
    n_eff = arrtot if weights is None else arrtot / np.maximum(weights,
                                                               1e-12)
    keep = n_eff >= MIN_REQUESTS - COUNT_SLACK
    if not keep.any():
        return float("nan")
    reps = np.concatenate([[0.0], np.sqrt(edges[:-1] * edges[1:]),
                           [edges[-1]]])
    w = reps + warm
    idx = np.flatnonzero(keep)
    logs = np.empty(len(idx))
    for b0 in range(0, len(idx), block):
        sel = idx[b0:b0 + block]
        h = hist[sel]
        pr = h / h.sum(axis=1, keepdims=True)
        # only the bins that hold mass enter the mixture's tail sum
        r, c = np.nonzero(pr)
        pv, wv = pr[r, c], w[c]
        log_med = np.log(np.maximum(dur_median[sel], 1e-9))[r]
        sig = np.maximum(dur_sigma[sel], 1e-6)[r]
        n = n_eff[sel]
        q_eff = (QUANTILE * (n - 1.0) + 1.0) / (n + 1.0)
        lo = np.ones(len(sel))
        hi = np.full(len(sel), 1.0 + w.max() / DUR_FLOOR + 1.0)
        for _ in range(60):
            mid = 0.5 * (lo + hi)
            x = wv / np.maximum(mid - 1.0, 1e-12)[r]
            sf = np.where(x <= DUR_FLOOR, 1.0, np.where(
                x >= DUR_CAP, 0.0,
                1.0 - _phi((np.log(np.maximum(x, 1e-300)) - log_med) / sig)))
            ok = np.bincount(r, pv * sf, minlength=len(sel)) >= q_eff
            hi = np.where(ok, mid, hi)
            lo = np.where(ok, lo, mid)
        logs[b0:b0 + block] = np.log(np.maximum(0.5 * (lo + hi), 1.0))
    if weights is None:
        return float(np.exp(logs.mean()))
    return float(np.exp(np.average(logs, weights=weights[idx])))
