"""Plain reference of the fluid engine: the parts every policy family shares.

A straightforward NumPy implementation of what the cells ask the
program to compute, written from the engine's published semantics and
importing nothing of it.  This module holds what does not depend on the
policy family:

* the per-tick arrival matrix of an event stream;
* the gap tables a keepalive's renewal-exact expiry reads (empirical for
  event streams, the Poisson forms for count matrices);
* long-tail clustering into weighted rate-medoid super-functions;
* the precision of a tick (``rounder``, ``ftz``);
* the summary's mixture solve: the p99 slowdown geomean of the delay x
  lognormal-duration mixture, at the per-function quantile the family's
  tail gives.

A family's fluid tick and the rest of its summary live in
``bench/references/<family>.py``, found by ``bench.lookup`` under the
configuration's ``policy.family``.  Such a module exposes
``simulate(wl, config, points, fleet, dtype)``: ``wl`` from ``prepare``
here, ``points`` the configuration's ``policy`` overlaid with each
checked traffic point, ``fleet`` the job entry's ``ELASTIC_FLEET``; it
returns one summary row per point.

Nothing here takes what the program made: it starts from the benchmark's
own workload arrays.  The tables are built and the summary is solved in
float64; a tick runs in the configuration's stated precision, float32,
or, with ``dtype="bfloat16"``, rounds every intermediate to bfloat16:
the benchmark's control, the reference put in the program's place at the
precision below the configuration's.
"""

from __future__ import annotations

import numpy as np

KA_GRID = np.geomspace(1e-3, 86_400.0, 56)
F32 = np.float32
TINY = np.finfo(F32).tiny
DUR_FLOOR, DUR_CAP = 0.02, 30.0
MIN_REQUESTS, COUNT_SLACK, QUANTILE = 5, 1e-3, 0.99


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
# the precision of a tick
# ---------------------------------------------------------------------------


def ftz(x):
    """Flush float32 subnormals to zero, as the chip's float32 (and XLA's
    CPU backend) does: the integral idle count takes the ceiling of a
    decaying in-service mass, which differs between a subnormal and 0."""
    return np.where(np.abs(x) < TINY, F32(0.0), x)


def rounder(dtype: str):
    """The rounding a tick applies after each operation in ``dtype``."""
    if dtype == "float32":
        return lambda x: x
    if dtype != "bfloat16":
        raise ValueError(f"unknown precision {dtype!r}")
    import ml_dtypes
    return lambda x: np.asarray(x, np.float32).astype(
        ml_dtypes.bfloat16).astype(np.float32)


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
                     edges, quantile, block: int = 4096) -> float:
    """Geomean over functions with at least five requests of the 99th
    percentile of S = 1 + (W + warm) / D, W from the function's delay
    histogram and D its clipped lognormal duration, at the per-function
    quantile ``quantile(n)`` of the family's tail for n requests; solved
    by 60 bisection steps."""
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
        q_eff = quantile(n)
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
