"""Vectorized (lax.scan) simulator: invariants + agreement with the oracle."""

import numpy as np
import pytest

from repro.core.cluster import Cluster
from repro.core.eventsim import EventSim, SimConfig
from repro.core.metrics import compute
from repro.core.policies import AsyncConcurrencyPolicy, SyncKeepalivePolicy
from repro.core.simjax import JaxPolicy, simulate_chunked
from repro.core.trace import TraceConfig, synthesize

TC = TraceConfig(num_functions=80, duration_s=1200, target_total_rps=12, seed=11)


@pytest.fixture(scope="module")
def trace():
    return synthesize(TC)


def test_simjax_invariants(trace):
    s = simulate_chunked(trace, JaxPolicy(kind=0, keepalive_s=120))
    assert s["slowdown_geomean_p99"] >= 1.0
    assert s["normalized_memory"] >= 1.0
    assert s["creation_rate"] >= 0.0
    assert 0.0 <= s["worker_share"] <= 1.0


def test_simjax_keepalive_monotone(trace):
    rows = [simulate_chunked(trace, JaxPolicy(kind=0, keepalive_s=ka))
            for ka in (30, 120, 600)]
    mem = [r["normalized_memory"] for r in rows]
    rate = [r["creation_rate"] for r in rows]
    assert mem == sorted(mem)
    assert rate == sorted(rate, reverse=True)


@pytest.mark.slow
def test_simjax_window_monotone(trace):
    rows = [simulate_chunked(trace, JaxPolicy(kind=1, window_s=w, target=0.7))
            for w in (30, 120, 600)]
    rate = [r["creation_rate"] for r in rows]
    assert rate == sorted(rate, reverse=True)
    mem = [r["normalized_memory"] for r in rows]
    assert mem == sorted(mem)


def test_simjax_target_direction(trace):
    lo = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.5))
    hi = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=1.0))
    # smaller target -> more instances -> more memory (paper Table 1)
    assert lo["normalized_memory"] >= hi["normalized_memory"]
    assert lo["instances_mean"] >= hi["instances_mean"]


@pytest.mark.slow
def test_simjax_tracks_oracle_trends(trace):
    """Same trace, same policies: the fluid simulator must order configs the
    same way as the discrete-event oracle (Spearman-style check)."""
    kas = [30, 120, 600]
    oracle = [compute(EventSim(trace, Cluster(8),
                               lambda f, ka=ka: SyncKeepalivePolicy(ka)).run())
              for ka in kas]
    fluid = [simulate_chunked(trace, JaxPolicy(kind=0, keepalive_s=ka))
             for ka in kas]
    for key_o, key_f in [("normalized_memory", "normalized_memory"),
                         ("creation_rate", "creation_rate"),
                         ("cpu_overhead", "cpu_overhead")]:
        a = np.argsort([getattr(m, key_o) for m in oracle])
        b = np.argsort([r[key_f] for r in fluid])
        assert (a == b).all(), (key_o, a, b)


def test_simjax_scales_to_thousands_of_functions():
    tc = TraceConfig(num_functions=2000, duration_s=600, target_total_rps=300,
                     seed=1)
    trace = synthesize(tc)
    s = simulate_chunked(trace, JaxPolicy(kind=1, window_s=60, target=0.7))
    assert np.isfinite(s["slowdown_geomean_p99"])
    assert s["instances_mean"] > 10


def test_import_raises_no_deprecation_warning():
    # the sharded dispatch uses jax.shard_map, not the deprecated
    # jax.experimental.shard_map module
    import os
    import pathlib
    import subprocess
    import sys
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(src)}
    subprocess.run([sys.executable, "-W", "error::DeprecationWarning", "-c",
                    "import repro.core.simjax"], env=env, check=True,
                   timeout=120)


@pytest.mark.parametrize("solver", ["numpy", "jax"])
def test_request_threshold_ignores_rounding(solver):
    # the cells router splits whole arrivals into fractional per-cell shares,
    # so a function's request count can come back one float32 step short of
    # min_requests; it still counts, or the geomean would depend on the
    # device's summation order
    from repro.core import simjax
    if solver == "numpy":
        p99 = simjax._slowdown_geomean
    else:
        def p99(*args):
            return simjax._slowdown_geomean_jax(*args, 5, 0.99, True, None)
    edges = simjax._delay_edges(256)
    hist = np.zeros((2, 256))
    hist[0, 0], hist[1, 0], hist[1, 150] = 20.0, 4.0, 1.0
    dm, ds = np.full(2, 0.5), np.full(2, 1.0)

    def geo(count):
        return p99(hist, np.array([20.0, count]), edges, dm, ds, 0.001)

    short = float(np.nextafter(np.float32(5.0), np.float32(0.0)))
    assert geo(short) == pytest.approx(geo(5.0), rel=1e-6)
    assert geo(4.0) != geo(5.0)          # a whole request short is out
