"""The cell router: weighted origin split, spill overflow, failover flux.

Routing is expressed as a (C, C) row-stochastic FLUX MATRIX M, where
``M[c, d]`` is the fraction of traffic originating at cell c that is
served by cell d this tick:

* an ALIVE cell keeps ``1 - s_c`` of its own traffic and spills ``s_c``
  (its overflow fraction, gated by the spill threshold) to warm siblings,
  distributed proportionally to their free warm slots — "the cheapest warm
  sibling" in fluid form;
* a DEAD cell's whole row is the failover distribution — survivors ordered
  by the same ``route_skew`` preference the origin weights use.

Every row sums to exactly 1 (mass conservation — pinned by
``tests/test_cells.py``), so the routed arrival matrix ``M^T arr``
redistributes, never creates or destroys, load.  The fluid engine traces this math inside the chunked scan
(``route_skew`` and ``spill_threshold`` are traced policy axes, hence
sweepable batch dimensions); the oracle uses the numpy twin to split
redirected arrivals at failover time.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_EPS = 1e-9


# ---------------------------------------------------------------------------
# numpy side (oracle / host precomputation)
# ---------------------------------------------------------------------------


def failover_dist_np(alive: np.ndarray, route_skew: float) -> np.ndarray:
    """(C,) redistribution over ALIVE cells, preference exp(-skew * c);
    uniform over alive cells when the skew weights underflow."""
    alive = np.asarray(alive, np.float64)
    w = alive * np.exp(-float(route_skew) * np.arange(len(alive)))
    tot = w.sum()
    if tot <= _EPS:
        n = max(alive.sum(), 1.0)
        return alive / n
    return w / tot


# ---------------------------------------------------------------------------
# traced side (fluid engine)
# ---------------------------------------------------------------------------


def failover_dist(alive, route_skew):
    """Traced twin of ``failover_dist_np`` (``route_skew`` may be a traced
    scalar — a sweepable axis)."""
    c = alive.shape[0]
    w = alive * jnp.exp(-route_skew * jnp.arange(c, dtype=jnp.float32))
    tot = w.sum()
    uniform = alive / jnp.maximum(alive.sum(), 1.0)
    return jnp.where(tot > _EPS, w / jnp.maximum(tot, _EPS), uniform)


def spill_fraction(queue_tot, arr_tot, warm_slots, threshold):
    """(C,) fraction of each cell's incoming traffic to spill: the backlog
    overflow above ``threshold`` queued-per-warm-slot, expressed as a
    fraction of this tick's arrivals, clipped to [0, 1].  threshold <= 0
    disables spill exactly (the parity scenarios run with it off)."""
    cap = threshold * jnp.maximum(warm_slots, 1.0)
    overflow = jnp.maximum(queue_tot + arr_tot - cap, 0.0)
    s = jnp.clip(overflow / jnp.maximum(arr_tot, _EPS), 0.0, 1.0)
    return jnp.where(threshold > 0.0, s, 0.0)


def flux_matrix(alive, spill, free_slots, fail_d):
    """(C, C) row-stochastic routing flux.

    ``alive``/``spill``/``free_slots`` are (C,); ``fail_d`` is the failover
    distribution over alive cells.  Spill from cell c lands on OTHER alive
    cells proportionally to their free warm slots; when no sibling has free
    capacity the spill stays home (the row falls back to the identity), so
    rows always sum to 1.
    """
    c = alive.shape[0]
    eye = jnp.eye(c, dtype=jnp.float32)
    pref = alive * jnp.maximum(free_slots, 0.0)
    others = pref[None, :] * (1.0 - eye)
    denom = others.sum(axis=1, keepdims=True)
    spill_rows = jnp.where(denom > _EPS,
                           others / jnp.maximum(denom, _EPS), eye)
    alive_rows = (1.0 - spill)[:, None] * eye + spill[:, None] * spill_rows
    return alive[:, None] * alive_rows \
        + (1.0 - alive)[:, None] * fail_d[None, :]
