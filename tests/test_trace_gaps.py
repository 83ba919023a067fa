"""``core/trace.gap_quantile``: the rolling-window gap quantile.

Pinned here: the batched selection returns, bit for bit, what evaluating
each rolling window with its own ``np.quantile`` call returned (the loop
is kept below, frozen, as the oracle), for every shape of function the
branch sees: too few arrivals, exactly one window, strides under 16 that
need not divide the window, no window in the measurement half, tied and
zero gaps, a long function and a mixed synthetic population, at four
quantiles and two window sizes."""

import functools

import numpy as np
import pytest

from repro.core.trace import (FunctionProfile, Trace, TraceConfig,
                              gap_quantile, synthesize)

DURATION = 1000.0


def frozen_gap_quantile(trace, q=0.99, window=256, stride=16):
    """The per-window loop ``gap_quantile`` replaced: one ``np.quantile``
    per rolling window, averaged over the measurement half."""
    out = np.full(trace.num_functions, trace.duration_s, np.float64)
    half = trace.duration_s / 2
    order = np.lexsort((trace.t, trace.fn))
    fn, t = trace.fn[order], trace.t[order]
    for i in range(trace.num_functions):
        ti = t[fn == i]
        if len(ti) < 2:
            continue
        g, gtime = np.diff(ti), ti[1:]
        if len(g) <= window:
            out[i] = np.quantile(g, q)
            continue
        ends = np.arange(window, len(g) + 1,
                         max(1, min(stride, (len(g) - window) // 8 + 1)))
        meas = ends[gtime[ends - 1] >= half]
        if len(meas) == 0:
            meas = ends[-1:]
        out[i] = np.mean([np.quantile(g[m - window:m], q) for m in meas])
    return out


def _trace(arrivals, duration_s=DURATION):
    """A Trace whose function i arrives at the times ``arrivals[i]``."""
    f = len(arrivals)
    t = np.concatenate([np.asarray(a, np.float64) for a in arrivals])
    fn = np.concatenate([np.full(len(a), i, np.int32)
                         for i, a in enumerate(arrivals)])
    order = np.argsort(t, kind="stable")
    prof = FunctionProfile(*(np.ones(f) for _ in range(5)))
    return Trace(t[order], fn[order], np.ones(len(t)), prof, duration_s)


def _uniform(rng, n, lo=0.0, hi=DURATION):
    return np.sort(rng.uniform(lo, hi, n))


def _few_arrivals(window, rng):
    return _trace([[], [10.0], [10.0, 700.0], _uniform(rng, 3)])


def _exactly_window(window, rng):
    return _trace([_uniform(rng, window + 1)])


def _window_plus_one(window, rng):
    return _trace([_uniform(rng, window + 2)])


def _short_strides(window, rng):
    # window + 1 .. window + 119 gaps: strides 1 to 15, most not dividing
    # the window
    return _trace([_uniform(rng, window + 1 + k)
                   for k in (1, 7, 8, 9, 30, 57, 100, 119)])


def _first_half_only(window, rng):
    # no window ends in the measurement half: the last window stands in
    return _trace([_uniform(rng, 3 * window, 0.0, DURATION / 2 - 1.0),
                   _uniform(rng, window + 20, 0.0, DURATION / 4)])


def _ties_and_zeros(window, rng):
    grid = np.sort(rng.integers(0, 100, 4 * window)).astype(np.float64)
    regular = np.arange(3 * window) * (DURATION / (3 * window))
    return _trace([grid * (DURATION / 100), regular,
                   np.repeat(_uniform(rng, window), 3)])


def _long_function(window, rng):
    return _trace([_uniform(rng, 13_001), _uniform(rng, 40)])


def _mixed_population(window, rng):
    # ~50k events over 200 functions: 50 above 256 gaps, 18 with fewer
    # than two arrivals, 11 with exactly two
    return synthesize(TraceConfig(num_functions=200, duration_s=DURATION,
                                  target_total_rps=50.0, seed=3))


CASES = {f.__name__[1:]: f for f in (
    _few_arrivals, _exactly_window, _window_plus_one, _short_strides,
    _first_half_only, _ties_and_zeros, _long_function, _mixed_population)}


@functools.cache
def _built(name, window):
    return CASES[name](window, np.random.default_rng(window))


@pytest.mark.parametrize("window", [64, 256])
@pytest.mark.parametrize("q", [0.5, 0.9, 0.99, 0.999])
@pytest.mark.parametrize("case", list(CASES))
def test_batched_windows_equal_the_per_window_loop(case, q, window):
    trace = _built(case, window)
    want = frozen_gap_quantile(trace, q, window)
    got = gap_quantile(trace, q, window)
    assert got.dtype == np.float64
    assert np.array_equal(got, want)
