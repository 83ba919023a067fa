"""The float64 summary solve (``simjax._slowdown_geomean_np``) evaluates the
slowdown mixture's survival function only on occupied histogram cells.

Pinned here: on every kind of histogram the solve returns exactly (``==``)
what the dense 60-pass bisection over every (function, bin) cell gives —
sparse and dense rows, bin 0 alone, cluster weights, the correlated tail,
the request threshold's edge, and no function kept."""

import math

import numpy as np
import pytest

from repro.core.simjax import (_COUNT_SLACK, _DUR_CAP, _DUR_FLOOR, _bin_reps,
                               _delay_edges, _phi, _slowdown_geomean_np)

NBINS = 256
EDGES = _delay_edges(NBINS)


def dense_geomean_np(hist, arrtot, edges, dur_median, dur_sigma, warm,
                     min_requests, q, iid_tail, fn_weights) -> float:
    """The solve as it was before the sparse evaluation: the survival
    function on every cell of the (F', B) histogram in each pass."""
    n_eff = np.asarray(arrtot, np.float64)
    if fn_weights is not None:
        n_eff = n_eff / np.maximum(np.asarray(fn_weights, np.float64), 1e-12)
    keep = n_eff >= min_requests - _COUNT_SLACK
    if not keep.any():
        return float("nan")
    h = np.asarray(hist)[keep]
    p = h / h.sum(axis=1, keepdims=True)
    w = _bin_reps(edges)[None, :] + warm
    log_med = np.log(np.maximum(dur_median[keep], 1e-9))[:, None]
    sig = np.maximum(dur_sigma[keep], 1e-6)[:, None]
    n = n_eff[keep]
    q_eff = (q * (n - 1.0) + 1.0) / (n + 1.0) if iid_tail \
        else np.full(len(n), q)
    lo = np.full(h.shape[0], 1.0)
    hi = np.full(h.shape[0], 1.0 + w.max() / _DUR_FLOOR + 1.0)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        x = w / np.maximum(mid - 1.0, 1e-12)[:, None]
        sf = np.where(x <= _DUR_FLOOR, 1.0,
                      np.where(x >= _DUR_CAP, 0.0,
                               1.0 - _phi((np.log(np.maximum(x, 1e-300))
                                           - log_med) / sig)))
        ok = (p * sf).sum(axis=1) >= q_eff
        hi = np.where(ok, mid, hi)
        lo = np.where(ok, lo, mid)
    logs = np.log(np.maximum(0.5 * (lo + hi), 1.0))
    if fn_weights is None:
        return float(np.exp(np.mean(logs)))
    return float(np.exp(np.average(
        logs, weights=np.asarray(fn_weights, np.float64)[keep])))


def _sparse_hist(rng, f, lo_bins=1, hi_bins=20):
    """Delay histograms with ``lo_bins``..``hi_bins`` occupied bins per row,
    most of the mass in bin 0 as in a mostly-warm replay."""
    hist = np.zeros((f, NBINS))
    for i in range(f):
        k = rng.integers(lo_bins, hi_bins + 1)
        bins = rng.choice(NBINS, size=k, replace=False)
        hist[i, bins] = rng.gamma(0.5, 3.0, size=k)
        hist[i, 0] += rng.uniform(5.0, 500.0)
    return hist


def _case(name, rng, f=120):
    """(hist, arrtot, dur_median, dur_sigma, warm, kwargs) of one case."""
    dur_median = rng.lognormal(-1.0, 1.2, f)
    dur_sigma = rng.uniform(0.2, 2.0, f)
    arrtot = rng.integers(1, 4000, f).astype(np.float64)
    warm, kw = 0.05, dict(min_requests=5, q=0.99, iid_tail=True,
                          fn_weights=None)
    hist = _sparse_hist(rng, f)
    if name == "dense":
        hist = rng.uniform(1e-3, 10.0, (f, NBINS))
    elif name == "bin0_only":
        hist[::2] = 0.0
        hist[::2, 0] = rng.uniform(1.0, 100.0, len(hist[::2]))
    elif name == "fn_weights":
        kw["fn_weights"] = rng.integers(1, 40, f).astype(np.float64)
    elif name == "correlated_tail":
        kw["iid_tail"] = False
    elif name == "threshold_row":
        arrtot[:] = 2.0
        arrtot[3] = 5 - _COUNT_SLACK
        arrtot[4] = np.nextafter(5 - _COUNT_SLACK, 0.0)
    elif name == "none_kept":
        arrtot[:] = 4.0
    elif name == "float32_no_warm":
        hist, warm = hist.astype(np.float32), 0.0
    return hist, arrtot, dur_median, dur_sigma, warm, kw


CASES = ("sparse", "dense", "bin0_only", "fn_weights", "correlated_tail",
         "threshold_row", "none_kept", "float32_no_warm")


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", CASES)
def test_sparse_solve_equals_the_dense_bisection(name, seed):
    rng = np.random.default_rng([seed, CASES.index(name)])
    hist, arrtot, med, sig, warm, kw = _case(name, rng)
    args = (hist, arrtot, EDGES, med, sig, warm, kw["min_requests"], kw["q"],
            kw["iid_tail"], kw["fn_weights"])
    got, want = _slowdown_geomean_np(*args), dense_geomean_np(*args)
    if name == "none_kept":
        assert math.isnan(got) and math.isnan(want)
        return
    assert got == want
    assert got > 1.0


def test_threshold_row_alone_enters_the_geomean():
    """Only the row at exactly ``min_requests - _COUNT_SLACK`` is kept:
    the geomean is that one function's quantile, and one float below it
    nothing is kept."""
    rng = np.random.default_rng(5)
    hist, arrtot, med, sig, warm, _ = _case("threshold_row", rng)
    one = _slowdown_geomean_np(hist[3:4], arrtot[3:4], EDGES, med[3:4],
                               sig[3:4], warm, 5, 0.99, True, None)
    assert _slowdown_geomean_np(hist, arrtot, EDGES, med, sig, warm, 5, 0.99,
                                True, None) == one
    assert math.isnan(_slowdown_geomean_np(
        hist[4:5], arrtot[4:5], EDGES, med[4:5], sig[4:5], warm, 5, 0.99,
        True, None))
