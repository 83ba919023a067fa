"""Fluid multi-cell engine: a leading cell axis in the chunked scan.

Same step math as ``repro.core.simjax`` — LITERALLY the same: every tick
each cell advances through the shared ``_make_step`` tick function (vmapped
over the cell axis, with per-cell state / arrivals / fleet / gap
statistics) — wrapped by the cells layer:

* the ROUTER: incoming per-cell arrivals are redistributed through a
  (C, C) row-stochastic flux matrix (``repro.cells.traffic``) — spill
  overflow to warm siblings, dead-cell traffic to the failover
  distribution.  ``route_skew`` and ``spill_threshold`` ride the traced
  policy params, so they are sweepable batch axes like any other knob.
* FAILOVER: at the (static) failure tick the dying cell's queued and
  in-flight mass re-queues on survivors along the failover distribution;
  from then on its state is alive-masked to zero and its fleet bounds
  collapse to (0, 0), so the dead region bills nothing and contributes
  nothing to the metric sums.
* TRIGGERS: host-precomputed scheduled floors (a (T, C) matrix chunked
  like the arrival tensor) and in-carry reactive threshold floors are
  applied as traced per-cell fleet ``min_nodes`` INSIDE the step — the
  fluid lowering of ``ConvergenceFleetPolicy``.

Accumulation mirrors ``simjax._chunk_impl``: one (F, nbins) delay
histogram summed ACROSS cells (function ids share one id space, so a
function's slowdown mixes its per-cell delay mixtures — exactly how the
oracle's combined record set reads), the 11 scalar sums alive-masked and
cell-summed, the measured-tick counter ``n`` bumped ONCE per tick, plus
per-cell partial sums for the attribution detail (``cell_rows``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.simjax import (_ACC_NAMES, _PFLEET, _delay_bin,
                               _drive_chunks, _init_state, _make_step,
                               _point_rows, _run_statics, stack_params)
from repro.core.trace import gap_statistics, rate_matrix
from repro.obs import runs

from repro.cells.topology import CellTopology
from repro.cells.traffic import failover_dist, flux_matrix, spill_fraction


def _cells_chunk_impl(state, arr_chunk, floor_chunk, lam0, gaps, alive_tab,
                      tail_tab, dur, mem, billed_w, pol, fleet, trig,
                      cpu_consts, static_nodes, edges, tick0, *,
                      warm_tick: int, total_ticks: int, family: str,
                      dt: float, cold_ticks: int, wbuf: int, prov_ticks: int,
                      has_fleet: bool, fail_cell: int, fail_tick: int,
                      route_skew_static: float, spill_static: float):
    """One time chunk of the C-cell simulation for ONE parameter point.

    ``state`` is ``(st, fr, ft, tcool)``: the per-cell simulator state
    pytree (every ``simjax._init_state`` leaf with a leading C axis) plus
    the reactive-trigger latches (held floor value / expiry tick / re-arm
    tick, each (C, K)).  ``arr_chunk`` is (T, C, F); ``floor_chunk`` the
    (T, C) scheduled-floor slice; ``trig`` the shared (K,) trigger
    constants (util_high, change, hold_ticks, cool_ticks) or None.
    """
    c_n, f = arr_chunk.shape[1], arr_chunk.shape[2]
    nbins = edges.shape[0] + 1
    has_reactive = trig is not None
    cells_ax = jnp.arange(c_n)
    rows_flat = jnp.tile(jnp.arange(f), c_n)
    # traced router knobs when the family declares them, topology statics
    # otherwise (a non-cells policy family can still run a topology)
    rs = pol["route_skew"] if "route_skew" in pol \
        else jnp.asarray(route_skew_static, jnp.float32)
    thr = pol["spill_threshold"] if "spill_threshold" in pol \
        else jnp.asarray(spill_static, jnp.float32)

    def alive_at(g):
        if fail_cell < 0:
            return jnp.ones(c_n), jnp.zeros(c_n)
        dead = (cells_ax == fail_cell)
        alive = 1.0 - (dead & (g >= fail_tick)).astype(jnp.float32)
        died = (dead & (g == fail_tick)).astype(jnp.float32)
        return alive, died

    def mask_state(st, alive):
        # dtype-preserving alive mask (the window cursor leaf is integer)
        return tuple(
            (x * alive.reshape((c_n,) + (1,) * (x.ndim - 1))).astype(x.dtype)
            for x in st)

    def one_cell(arr_row, st_c, fl_c, l0_c, gp_c, at_c, tt_c, sn_c):
        step = _make_step(arr_row[None, :], dur, mem, billed_w, l0_c, gp_c,
                          (at_c, tt_c), pol, fl_c, cpu_consts, sn_c,
                          family=family, dt=dt, cold_ticks=cold_ticks,
                          wbuf=wbuf, prov_ticks=prov_ticks,
                          has_fleet=has_fleet)
        return step(st_c, 0)

    def acc_step(carry, xs):
        st, fr, ft, tcool, hist, arrtot, sums, n, csums, cn = carry
        a_t, fsched_t, i = xs
        g = tick0 + i
        alive, died = alive_at(g)
        # failover: harvest the dying cell's backlog + in-flight mass
        # BEFORE masking and re-inject it on survivors as retry ARRIVALS
        # at the failure tick (the fluid twin of the oracle's retry
        # re-injection).  Arrival injection — not queue injection — is
        # load-bearing: the delay histogram only records mass that enters
        # through the arrival path, and the retry cohort's post-failover
        # delays are exactly what the oracle's survivor records carry.
        # (The cohort's pre-failure arrival entries stay in the histogram
        # — a forward-only scan cannot retract them the way the oracle
        # drops its ghost records — so the retried share is counted at
        # both its optimistic pre-fail and its true post-fail delay; the
        # measured parity band absorbs this.)
        # The two cell-axis contractions here are broadcast-multiply-sums,
        # not einsums: a float32 dot at default precision runs as one
        # bfloat16 pass on a TPU, which would round per-tick arrival counts
        # above 256 and break the router's flow conservation.  With C <= 16
        # cells the elementwise form is cheap, and exact in float32.
        moved = (died[:, None] * (st[1] + st[2])).sum(0)
        st = mask_state(st, alive)
        fail_d = failover_dist(alive, rs)
        # router from previous-tick state
        slots = st[0].sum(-1) * pol["cc"]
        free = jnp.maximum(slots - st[1].sum(-1), 0.0)
        s = spill_fraction(st[2].sum(-1), a_t.sum(-1), slots, thr) * alive
        flux = flux_matrix(alive, s, free, fail_d)
        routed = (flux[:, :, None] * a_t[:, None, :]).sum(0) \
            + fail_d[:, None] * moved[None, :]
        # per-cell fleet bounds: scheduled + reactive floors raise
        # min_nodes; a dead cell's bounds collapse to (0, 0)
        if has_reactive:
            floor_r = jnp.where(g < ft, fr, 0.0).max(axis=1)
        else:
            floor_r = jnp.zeros(c_n)
        if has_fleet:
            min_eff = jnp.maximum(jnp.maximum(fleet[0], fsched_t),
                                  floor_r) * alive
            fleet_cells = jnp.concatenate(
                [min_eff[:, None], (fleet[1] * alive)[:, None],
                 jnp.broadcast_to(fleet[2:], (c_n, fleet.shape[0] - 2))],
                axis=1)
        else:
            fleet_cells = jnp.broadcast_to(fleet, (c_n, fleet.shape[0]))
        with jax.named_scope("fluid_tick"):
            st, ys = jax.vmap(one_cell)(routed, st, fleet_cells, lam0, gaps,
                                        alive_tab, tail_tab, static_nodes)
        # reactive triggers read this tick's utilization; the raised floor
        # binds from the NEXT tick (a one-tick actuation lag, matching the
        # oracle's once-per-tick reconcile)
        if has_reactive and has_fleet:
            util_high, change, hold_ticks, cool_ticks = trig
            util = ys[4] / jnp.maximum(ys[10] * fleet[5], 1e-9)
            can = (util[:, None] >= util_high[None, :]) & (g >= tcool) \
                & (alive[:, None] > 0.0)
            fr = jnp.where(can, ys[10][:, None] + change[None, :], fr)
            ft = jnp.where(can, (g + hold_ticks[None, :]).astype(ft.dtype),
                           ft)
            tcool = jnp.where(can,
                              (g + cool_ticks[None, :]).astype(tcool.dtype),
                              tcool)
        # accumulate: histogram mass per (function), scalars alive-masked
        # and cell-summed, n bumped ONCE per tick (not per cell)
        m = ((g >= warm_tick) & (g < total_ticks)).astype(jnp.float32)
        delay, arr, arr_delayed = ys[0], ys[1], ys[2]
        wmask = m * alive[:, None]
        with jax.named_scope("delay_histogram"):
            b = _delay_bin(edges, delay.reshape(-1), nbins)
            hist = hist.at[rows_flat, b].add(
                (arr_delayed * wmask).reshape(-1))
            hist = hist.at[:, 0].add(((arr - arr_delayed) * wmask).sum(0))
        arrtot = arrtot + (arr * wmask).sum(0)
        ysc = jnp.stack(ys[3:3 + len(_ACC_NAMES)]) * alive[None, :]
        return (st, fr, ft, tcool, hist, arrtot,
                sums + m * ysc.sum(-1), n + m, csums + m * ysc.T,
                cn + m * alive), None

    st, fr, ft, tcool = state
    init = (st, fr, ft, tcool, jnp.zeros((f, nbins)), jnp.zeros(f),
            jnp.zeros(len(_ACC_NAMES)), jnp.zeros(()),
            jnp.zeros((c_n, len(_ACC_NAMES))), jnp.zeros(c_n))
    xs = (arr_chunk, floor_chunk, jnp.arange(arr_chunk.shape[0]))
    carry, _ = jax.lax.scan(acc_step, init, xs)
    return carry[:4], carry[4:]


def _cells_chunk_batch_impl(state, arr_chunk, floor_chunk, lam0, gaps,
                            alive_tab, tail_tab, dur, mem, billed_w, pols,
                            fleets, trig, cpu_consts, static_nodes, edges,
                            tick0, **statics):
    """One time chunk for a batch of parameter points (vmap over the point
    axis of state/pols/fleets, every per-cell input shared).  The body runs
    once per trace, which the open engine run counts as ``step_traces``."""
    runs.count("step_traces", 1)

    def one(st, p, fl):
        return _cells_chunk_impl(st, arr_chunk, floor_chunk, lam0, gaps,
                                 alive_tab, tail_tab, dur, mem, billed_w,
                                 p, fl, trig, cpu_consts, static_nodes,
                                 edges, tick0, **statics)
    return jax.vmap(one)(state, pols, fleets)


_cells_chunk_batch = partial(jax.jit, static_argnames=(
    "warm_tick", "total_ticks", "family", "dt", "cold_ticks", "wbuf",
    "prov_ticks", "has_fleet", "fail_cell", "fail_tick",
    "route_skew_static", "spill_static"),
    donate_argnums=(0,))(_cells_chunk_batch_impl)


def cells_chunked_summaries(traces, topo: CellTopology, policy, pols,
                            fleets, *, sim, dt: float, num_nodes: int,
                            provision_s: float, has_fleet: bool,
                            chunk_ticks: int, warmup_frac: float = 0.5,
                            nbins: int = 256, billing=None,
                            detail: Optional[dict] = None) -> list:
    """The multi-cell twin of ``simjax._chunked_summaries`` through the same
    host path: per point, one row summed over the cells' traces (from
    ``build_cell_traces``, one shared function id space); a ``detail`` dict
    receives ``cell_rows``, point 0's per-cell attribution partials."""
    c_n = topo.cell_count
    if len(traces) != c_n:
        raise ValueError(f"got {len(traces)} cell traces for a "
                         f"{c_n}-cell topology")
    if (topo.scheduled or topo.reactive) and not has_fleet:
        raise ValueError("cell triggers drive the node fleet: the scenario "
                         "needs a fleet for scheduled/reactive triggers")
    with runs.run(points=int(fleets.shape[0]), devices=0, cells=c_n) as info:
        with runs.span("prep.rate_matrix"):
            mats = [np.asarray(rate_matrix(tr, dt)) for tr in traces]
            arr_np = np.stack(mats, axis=1)                 # (T, C, F)
        n_ticks, _, f = arr_np.shape
        chunk_ticks = max(1, min(chunk_ticks, n_ticks))
        info.update(functions=f, ticks=n_ticks, chunk_ticks=chunk_ticks)
        gq_l, at_l, tt_l = zip(*(gap_statistics(tr) for tr in traces))

        with runs.span("prep.device"):
            # profile-wide constants: every cell shares the function ids
            st = _run_statics(traces[0], policy, sim, dt, billing=billing,
                              provision_s=provision_s, nbins=nbins,
                              n_ticks=n_ticks, warmup_frac=warmup_frac)
            # (C, F) rates and gap quantiles, (C, F, K) gap tables
            lam0, gaps, alive_tab, tail_tab = (
                jnp.asarray(np.stack(x), jnp.float32)
                for x in ([m.mean(axis=0) / dt for m in mats], gq_l, at_l,
                          tt_l))
            ft_s = topo.fail_time(traces[0].duration_s)
            fail_tick = -1 if ft_s is None else int(round(ft_s / dt))
            # (T, C) scheduled node floors
            floor_np = topo.floor_schedule(n_ticks, dt, traces[0].duration_s)
            k = len(topo.reactive)
            # (util_high, change, hold_ticks, cool_ticks), each (K,)
            trig = tuple(jnp.asarray(v, jnp.float32) for v in zip(*(
                (t.util_high, t.change, max(1, round(t.hold_s / dt)),
                 max(1, round(t.cooldown_s / dt))) for t in topo.reactive))
            ) if k else None
            static_nodes = jnp.asarray(topo.cell_nodes(num_nodes),
                                       jnp.float32)
            edges_j = jnp.asarray(st.edges)
            fleets_j = jnp.asarray(fleets, jnp.float32)
            pols_j = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                                  pols)

            def init_point(fl):
                cells = jax.vmap(lambda sn: _init_state(
                    f, st.cold_ticks, st.wbuf, st.prov_ticks,
                    fl[0] if has_fleet else sn))(static_nodes)
                return (cells,) + tuple(jnp.zeros((c_n, k)) for _ in range(3))

            state = jax.vmap(init_point)(fleets_j)

        def dispatch(state, chunks, t0):
            return _cells_chunk_batch(
                state, *chunks, lam0, gaps, alive_tab, tail_tab, st.dur,
                st.mem, st.billed_w, pols_j, fleets_j, trig, st.cpu_consts,
                static_nodes, edges_j, t0, warm_tick=st.warm_tick,
                total_ticks=n_ticks, family=policy.family, dt=dt,
                cold_ticks=st.cold_ticks, wbuf=st.wbuf,
                prov_ticks=st.prov_ticks, has_fleet=has_fleet,
                fail_cell=int(topo.fail_cell), fail_tick=fail_tick,
                route_skew_static=float(topo.route_skew),
                spill_static=float(topo.spill_threshold))

        acc = _drive_chunks(state, (arr_np, floor_np), dispatch,
                            n_ticks=n_ticks, chunk_ticks=chunk_ticks)
        rows = _point_rows(acc, st, policy.family, sim, dt, f=f)
    if detail is not None:
        detail["cell_rows"] = _cell_rows(acc[4][0], acc[5][0], dt)
    return rows


def _cell_rows(csums, cn, dt: float) -> list:
    """Per-cell attribution partials (point 0): where the node-seconds and
    churn CPU of a multi-region run actually accrue — the cells extension
    of the overhead-attribution ledger."""
    out = []
    for c in range(csums.shape[0]):
        s = dict(zip(_ACC_NAMES, csums[c]))
        ticks = max(float(cn[c]), 1e-9)
        out.append({
            "cell": c,
            "ticks_alive": float(cn[c]),
            "instances_mean": float(s["instances"] / ticks),
            "nodes_mean": float(s["nodes"] / ticks),
            "node_seconds": float(s["nodes"] * dt),
            "spot_node_seconds": float(s["spot_nodes"] * dt),
            "creations": float(s["creations"]),
            "completed": float(s["completions"]),
            "cpu_worker_s": float(s["cpu_worker"]),
            "cpu_master_s": float(s["cpu_master"]),
            "cpu_useful_s": float(s["useful"]),
            "billed_gb_s": float(s["billed_gb_s"]),
            "mem_total_mean": float(s["mem_total"] / ticks),
        })
    return out


def run_cells_fluid(sc, traces, sim, *, billing=None,
                    detail: Optional[dict] = None) -> dict:
    """Single-point fluid replay of a cells scenario (the runner's simjax
    leg).  Returns one ``simulate_chunked``-style metric row."""
    policy = sc.policy.to_jax()
    has_fleet = sc.fleet is not None
    pols = stack_params([policy.params()])
    fleets = np.asarray([sc.fleet.params() if has_fleet
                         else np.zeros(len(_PFLEET))], np.float32)
    return cells_chunked_summaries(
        traces, sc.cells, policy, pols, fleets, sim=sim, dt=sim.tick_s,
        num_nodes=sc.num_nodes,
        provision_s=sc.fleet.provision_s if has_fleet else 0.0,
        has_fleet=has_fleet, chunk_ticks=sc.chunk_ticks,
        billing=billing, detail=detail)[0]
