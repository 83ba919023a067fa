"""Vectorized cluster simulator: the whole fleet as one ``jax.lax.scan``.

This is the KWOK analogue (paper §3.4): the *policy math is identical* to the
real control plane (same window average / utilization target / keepalive
semantics), while workers are simulated — so experiments scale to thousands
of functions and hundreds of nodes in seconds, jit-compiled.

Two-level autoscaling: when a ``JaxFleet`` is passed, the node fleet joins
the scan carry — a scalar node count, a provisioning pipeline (provision
latency ≫ cold start), and a scale-down cooldown timer — mirroring
``repro.fleet.UtilizationFleetPolicy`` + ``NodeFleet`` branchlessly.
Instance creation is then capped by node capacity (capped creates stay
queued and re-request, the fluid analogue of placement-failure deferral),
and unplaceable demand feeds the node reconciler, so placement pressure
scales the fleet up instead of dropping requests.  When the policy family
declares the spot axes (``spot_fraction`` / ``hazard_per_hour`` — read off
the policy params like ``cc``), the fleet splits across an on-demand and a
spot tier and a traced hazard flux evicts spot capacity each tick: warm
instances on reclaimed nodes die, in-flight work outliving the reclaim
notice re-queues, and the spot share bills separately — the fluid twin of
``repro.fleet.spot``.

Numeric policy and fleet parameters are *traced*, not compile-time
constants, so ``repro.fleet.sweep`` can ``vmap`` thousands of policy
configurations through one compiled scan (the fast path behind the Fig. 8 /
Fig. 10 trade-off frontiers).  Only structural sizes (window buffer,
cold-start/provision pipeline depths, the policy FAMILY name) are static.

Policies dispatch through ``repro.core.policy_api``: the scan asks the
registered family for one pure ``decide(params, PolicyObs) -> JaxDecision``
call per tick, with ``params`` a traced PYTREE ({axis: leaf}) rather than a
fixed four-knob vector — a learned policy's weight pytree batches exactly
like a keepalive scalar.  Family metadata (synchronous tails, async cold
factor, window-buffer use) replaces the per-kind special cases that used to
be duplicated here and in ``repro.opt``.

Approximations vs the discrete-event oracle (validated in tests):
* fluid service: completions per tick = in_service * dt / mean_dur_f
  (memoryless service), fractional instances allowed; dispatch credits
  within-tick slot turnover (the oracle hands requests to instances the
  moment they free);
* keepalive expiry as a renewal-matched flux: rate lam/(e^{lam*ka}-1) per
  idle instance reproduces the oracle's continuous-idleness timer in
  expectation for Poisson gaps (1/ka as lam->0, ~never for chatty fns);
* per-tick queue-delay estimator (backlog position / drain rate + residual
  cold-start wait) applies only to the arrivals NOT served warm that tick;
  per-function p99 slowdown comes from a (delay histogram x lognormal
  duration) mixture with a finite-sample percentile correction, matching
  the oracle's per-request empirical percentile;
* scale-down removes (cooldown-gated) idle node capacity instantly; the
  oracle drains the emptiest nodes first, so the residual drain time is
  small (parity-tested within 15%, see tests/test_scenarios.py).

State is (F,)-vectorized; policies are branchless jnp.  dt = 1s.
"""

from __future__ import annotations

import dataclasses
import sys
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.eventsim import SimConfig
from repro.core.policy_api import (HYBRID_MIN_KA_S, PolicyObs,  # noqa: F401
                                   get_family)
from repro.core.trace import gap_statistics, rate_matrix
from repro.distributed.sharding import device_mesh
from repro.obs import runs
from repro.obs.telemetry import TELEM_ATTR, TELEM_SERIES, assemble_telemetry


@dataclasses.dataclass(frozen=True)
class JaxPolicy:
    """One traced policy configuration: a registered FAMILY plus its params.

    ``family`` names a ``repro.core.policy_api`` registry entry ("sync",
    "async", "hybrid", "learned", ...); the legacy integer ``kind`` is kept
    as an alias (0/1/2/3) and either selector may be given.  ``params()``
    lowers the declared axes to the traced params PYTREE the scan consumes
    — every leaf (scalar knob or weight array) is a sweepable/learnable
    batch axis.  Only ``family`` and ``window_s`` (the window buffer depth)
    are structural.  Knob values are validated against the family's
    declared bounds at construction: a NaN or out-of-range keepalive fails
    HERE, not at the end of a scan."""
    kind: int = -1
    keepalive_s: float = 600.0
    window_s: float = 60.0
    target: float = 0.7
    cc: int = 1
    prewarm_s: float = 0.0
    family: str = ""
    theta: Any = None          # learnable pytree (learned family)
    extra: Any = None          # {axis: value} for axes beyond these fields

    def __post_init__(self):
        if not self.family:
            if self.kind < 0:
                raise ValueError("JaxPolicy needs a family name or a "
                                 "legacy kind")
            object.__setattr__(self, "family", get_family(self.kind).name)
        fam = get_family(self.family)      # raises KeyError on unknown names
        if fam.kind is not None:
            object.__setattr__(self, "kind", fam.kind)
        for nm in ("keepalive_s", "window_s", "target", "cc", "prewarm_s"):
            if not np.isfinite(float(getattr(self, nm))):
                raise ValueError(f"JaxPolicy.{nm} is not finite: "
                                 f"{getattr(self, nm)!r}")
        if self.window_s <= 0:
            raise ValueError(f"JaxPolicy.window_s must be > 0, got "
                             f"{self.window_s!r}")
        fam.validate(self.params())

    def params(self) -> dict:
        """The traced params pytree ({axis name: leaf})."""
        return get_family(self.family).init_params(self)


@dataclasses.dataclass(frozen=True)
class JaxFleet:
    """Node-fleet layer parameters (mirrors UtilizationFleetPolicy +
    NodeFleet).  ``provision_s`` is structural (pipeline depth, static);
    the rest are traced and sweepable.  ``reclaim_notice_s`` is the spot
    tier's eviction warning (repro.fleet.spot); it only acts when the
    policy family declares the spot axes (spot_fraction / hazard_per_hour
    — the engine reads them off the policy params, like ``cc``)."""
    node_memory_mb: float = 192_000.0
    provision_s: float = 60.0
    min_nodes: float = 1.0
    max_nodes: float = 64.0
    util_target: float = 0.7
    warm_frac: float = 0.25
    cooldown_s: float = 120.0
    reclaim_notice_s: float = 120.0

    def params(self) -> np.ndarray:
        """The traced parameter vector (see _PFLEET indices)."""
        return np.asarray([self.min_nodes, self.max_nodes, self.util_target,
                           self.warm_frac, self.cooldown_s,
                           self.node_memory_mb, self.reclaim_notice_s],
                          np.float32)


# traced fleet parameter vector layout (policy params are a pytree now —
# see repro.core.policy_api; the fleet layer keeps its fixed vector)
_PFLEET = ("min_nodes", "max_nodes", "util_target", "warm_frac",
           "cooldown_s", "node_memory_mb", "reclaim_notice_s")


def _init_state(f, cold_ticks, wbuf, prov_ticks, init_nodes):
    # the four trailing leaves are the spot tier (node count, provisioning
    # pipeline, instance mass resident on spot capacity, evicted-warm
    # deficit); they stay identically zero unless the policy family
    # declares the spot axes
    return (jnp.zeros(f), jnp.zeros(f), jnp.zeros(f),
            jnp.zeros((f, cold_ticks)), jnp.zeros((f, wbuf)), jnp.asarray(0),
            init_nodes * jnp.ones(()), jnp.zeros(prov_ticks), jnp.zeros(()),
            jnp.zeros(()), jnp.zeros(prov_ticks), jnp.zeros(f),
            jnp.zeros(f))


def _make_step(arrivals, dur, mem, billed_w, lam0, gaps, gap_tab, pol, fleet,
               cpu_consts,
               static_nodes, *, family: str, dt: float, cold_ticks: int,
               wbuf: int, prov_ticks: int, has_fleet: bool,
               telem: bool = False, weights=None):
    """One simulated tick, shared by the chunked-summary scans
    (`_chunk_impl`, `cells.fluid._cells_chunk_impl`) and the learned
    policy's loss, so the policy math exists once.

    ``lam0`` is the (F,) long-run mean arrival rate per function, the
    input to the renewal-matched keepalive expiry (see
    ``policy_api.renewal_expiry_rate``).  A windowed estimate would adapt
    to regime changes, but its per-arrival spikes are huge relative to
    sparse functions' rates and bias the (convex) expiry rate exactly while
    an instance is alive; the stationary mean is exact for the
    Poisson-renewal model (trace parity holds within a few percent for
    Poisson gaps; strongly bursty gap distributions under SHORT keepalives
    under-expire somewhat — see EXPERIMENTS.md).

    All of ``pol`` (a params PYTREE — scalar knobs or weight arrays) is
    traced, so the frontier engine can vmap over any leaf; only ``family``
    (the registry key) selects the compiled decide branch.

    ``weights`` is the (F,) super-function multiplicity from the clustering
    preprocessor (repro.scenarios.cluster): each function's PER-FUNCTION
    dynamics are those of one representative member, while every
    cross-function coupling and metric sum — node capacity pressure, CPU
    churn, the scalar accumulators — is linear in per-function
    contributions and therefore weighted by the member count.  This is
    exact when members are identical (they evolve identically in the fluid
    limit).  ``weights=None`` emits LITERALLY the unweighted ops, keeping
    the bit-for-bit baseline.
    """
    f = dur.shape[0]
    fam = get_family(family)
    ccf = pol["cc"]
    ws = (lambda v: v) if weights is None else (lambda v: v * weights)
    # the engine reads the spot axes off the policy params exactly like
    # ``cc``: a family that never declares them runs the original
    # single-tier fleet math (the spot carries stay identically zero)
    has_spot = has_fleet and "spot_fraction" in pol

    def step(state, tick):
        (inst, in_service, queue, starting, win, wcur,
         nodes, pipe, cool, nodes_spot, pipe_spot, spot_inst,
         evict_deficit) = state
        arr = arrivals[tick].astype(jnp.float32)

        if has_fleet:
            # provisioning completes
            nodes = nodes + pipe[0]
            pipe = jnp.concatenate([pipe[1:], jnp.zeros((1,))])
            if has_spot:
                nodes_spot = nodes_spot + pipe_spot[0]
                pipe_spot = jnp.concatenate([pipe_spot[1:], jnp.zeros((1,))])

        # instances finishing cold start
        ready = starting[:, 0]
        inst = inst + ready
        starting = jnp.concatenate([starting[:, 1:], jnp.zeros((f, 1))], axis=1)

        # dispatch + fluid service.  Dispatch capacity credits the slot
        # turnover expected WITHIN this tick (the oracle hands a request to
        # an instance the moment it frees, not at tick boundaries); the
        # momentary in_service overshoot is removed by the completions flux.
        slots = inst * ccf
        turnover = jnp.minimum(in_service * dt / dur, in_service)
        free = jnp.maximum(slots - in_service, 0.0)
        dispatch = jnp.minimum(queue + arr, free + turnover)
        # FIFO: backlog dispatches first; whatever of THIS tick's arrivals
        # doesn't fit waits (cold start / queue) — the delay estimate below
        # applies only to this delayed share, warm hits see ~zero wait
        arr_delayed = arr - jnp.maximum(dispatch - queue, 0.0)
        in_service = in_service + dispatch
        queue = queue + arr - dispatch
        completions = jnp.minimum(in_service * dt / dur, in_service)
        in_service = in_service - completions

        # Busy memory sample: expected busy-instance count time-averaged
        # over the tick.  Completed work was present for min(dur, dt) of the
        # tick, survivors for all of it — in steady state this recovers the
        # continuous-time E[#busy] = lambda*dur exactly in both the dur<dt
        # and dur>dt regimes.  A ceil here would charge a full instance to
        # every fractional in-service tail and overcount busy memory 10x+
        # on sparse functions.  The policy-facing idle count below stays
        # integral (ceil) — the oracle can only retire instances with ZERO
        # in-flight requests at the tick instant.
        served_avg = in_service + completions * jnp.minimum(dur / dt, 1.0)
        # cc > 1 packing: the oracle charges a partially-occupied instance's
        # memory as FULLY busy, so expected busy instances is ~ceil(B/cc)
        # under its first-free (packing) dispatch, not B/cc slot-utilization.
        # The smooth analogue B/cc + (1-1/cc)(1-e^-B) is exact at cc=1 and
        # reproduces the one-partial-instance bin for sparse load; remaining
        # cc>1 gaps are documented in EXPERIMENTS.md (frontier envelope).
        packed = served_avg / ccf + (1.0 - 1.0 / ccf) * -jnp.expm1(-served_avg)
        busy_inst = jnp.minimum(inst, packed)
        # two idle views: the EXPECTED idle mass (fractional — drives the
        # sync expiry flux; a ceil would pin idle to zero for as long as any
        # exponential in-service tail persists, i.e. forever for dur > dt)
        # and the INTEGRAL idle count (drives the async retire cap — the
        # oracle only retires instances with zero in-flight requests)
        idle_frac = jnp.maximum(inst - jnp.minimum(inst, in_service / ccf), 0.0)
        idle = jnp.maximum(inst - jnp.minimum(inst, jnp.ceil(in_service / ccf)),
                           0.0)
        # window concurrency is the end-of-tick snapshot (in-flight +
        # backlog), mirroring what the oracle's reconcile tick observes
        concurrency = in_service + queue

        # ---- instance-level policy: registry dispatch ----
        win_ = win.at[:, wcur % wbuf].set(concurrency)
        n_valid = jnp.minimum(wcur + 1, wbuf).astype(jnp.float32)
        avg = win_.sum(axis=1) / n_valid

        pending = starting.sum(axis=1)
        if has_fleet:
            # queued demand not already covered by in-flight cold starts
            # re-requests creation — capacity-capped creates retry here
            demand = jnp.maximum(queue - pending * ccf, 0.0)
        else:
            demand = jnp.maximum(arr - (free + pending), 0.0)
        obs = PolicyObs(arr=arr, queue=queue, inst=inst, pending=pending,
                        idle=idle, idle_frac=idle_frac, free=free, avg=avg,
                        demand=demand, lam=lam0, gap_p99=gaps,
                        alive_tab=gap_tab[0], tail_tab=gap_tab[1], dt=dt)
        dec = fam.decide(pol, obs)
        create, retire = dec.create, dec.retire

        inst = inst - retire

        # ---- node-fleet layer ----
        if has_fleet:
            min_n, max_n, util_t, warm_f, cool_s, node_mem = (
                fleet[0], fleet[1], fleet[2], fleet[3], fleet[4], fleet[5])

            # spot eviction flux: each UP spot node is reclaimed at the
            # hazard rate; warm instances on reclaimed capacity die (the
            # fleet spreads instances uniformly, so the instance loss is
            # the evicted capacity fraction).  In-flight work whose
            # memoryless remaining service outlives the reclaim notice
            # re-queues (the rest completes while the node drains, as the
            # oracle lets it).  The oracle recreates each killed warm
            # instance on its function's NEXT ARRIVAL — a cold start — so
            # the killed mass parks in ``evict_deficit`` and drains back
            # into creation at the arrival rate: the eviction-driven
            # cold-start storm.  The evicted node bills through its notice
            # window.
            if has_spot:
                notice = fleet[6]
                h_tick = -jnp.expm1(-(pol["hazard_per_hour"] / 3600.0) * dt)
                evict = nodes_spot * h_tick
                # the mass at risk is what actually RESIDES on spot
                # capacity (``spot_inst``): evicted spot nodes are young —
                # mean lifetime 1/hazard — so they only hold instances
                # placed since they booted, not a uniform 1/nodes share of
                # the fleet.  Each spot node evicts with probability
                # h_tick, taking its resident share with it.
                spot_inst = spot_inst \
                    * jnp.clip(1.0 - retire / jnp.maximum(inst + retire,
                                                          1e-9), 0.0, 1.0)
                spot_inst = jnp.minimum(spot_inst, inst)
                killed = spot_inst * h_tick
                spot_inst = spot_inst - killed
                inst = inst - killed
                # in-flight work rides the same resident share; whatever
                # outlives the reclaim notice re-queues
                evict_frac = killed / jnp.maximum(inst + killed, 1e-9)
                requeue = in_service * evict_frac * jnp.exp(-notice / dur)
                in_service = in_service - requeue
                queue = queue + requeue
                # a sync arrival recreates a killed instance iff it finds
                # no free slot: conditioned on one whole instance missing,
                # the surviving free capacity is (inst + deficit - 1 +
                # pending - busy slots).  The blocked-arrival probability
                # falls geometrically per surviving free slot (each spare
                # is busy with odds a/(1+a) at offered load a, the
                # coincidence that birthed it in the first place), so even
                # a killed EXCESS instance regenerates at the next
                # concurrency peak within the keepalive — exactly how the
                # oracle's per-arrival create maintains its equilibrium.
                pool = evict_deficit + killed
                free_cond = jnp.maximum(
                    inst + pool - 1.0 + pending - in_service / ccf, 0.0)
                a = in_service / ccf
                p_need = (a / (1.0 + a)) ** free_cond
                drain = pool * -jnp.expm1(-lam0 * dt)
                rec = drain * p_need
                evict_deficit = pool - rec
                # sync semantics: every arrival queued DURING the recreate's
                # cold start also creates (one sandbox per concurrent
                # request), so each recreate overshoots by ~lam x cold —
                # excess instances that then idle a full keepalive
                evict_rec = rec * (1.0 + lam0 * cold_ticks * dt)
                create = create + evict_rec
                nodes_spot = nodes_spot - evict
                evict_bill = evict * notice / dt
            else:
                killed = jnp.zeros(f)
                evict_bill = jnp.zeros(())

            capacity_mb = (nodes + nodes_spot) * node_mem
            committed = ws((inst + starting.sum(axis=1)) * mem).sum()
            free_mb = jnp.maximum(capacity_mb - committed, 0.0)
            req_mb = ws(create * mem).sum()
            scale = jnp.minimum(1.0, free_mb / jnp.maximum(req_mb, 1e-9))
            create = create * scale
            starting = starting.at[:, cold_ticks - 1].add(create)
            if has_spot:
                # round-robin first-fit walks the node list and takes the
                # first node with space — uniform by NODE COUNT while
                # nodes have room (free-capacity weighting would cascade
                # recreated mass straight back onto young spot nodes)
                cap_share = nodes_spot / jnp.maximum(nodes + nodes_spot,
                                                     1e-9)
                spot_inst = spot_inst + create * cap_share

            # reconcile: used memory plus unplaceable pressure -> desired
            # nodes, split across tiers at the policy's spot fraction
            used = ws((inst + starting.sum(axis=1)) * mem).sum()
            pressure = jnp.maximum(req_mb * (1.0 - scale), 0.0)
            needed = jnp.ceil((used + pressure) / (util_t * node_mem) - 1e-9)
            warm = jnp.ceil(warm_f * jnp.maximum(needed, 1.0) - 1e-9)
            desired_n = jnp.clip(needed + warm, min_n, max_n)
            desired_spot = jnp.round(desired_n * pol["spot_fraction"]) \
                if has_spot else jnp.zeros(())
            desired_od = desired_n - desired_spot
            have_od = nodes + pipe.sum()
            have_spot = nodes_spot + pipe_spot.sum()
            up = jnp.maximum(desired_od - have_od, 0.0)
            pipe = pipe.at[prov_ticks - 1].add(up)
            up_spot = jnp.maximum(desired_spot - have_spot, 0.0)
            pipe_spot = pipe_spot.at[prov_ticks - 1].add(up_spot)
            down_want = jnp.maximum(have_od - desired_od, 0.0)
            down_want_spot = jnp.maximum(have_spot - desired_spot, 0.0)
            max_down = jnp.maximum(nodes + nodes_spot
                                   - jnp.ceil(used / node_mem), 0.0)
            # each tier can only terminate its own UP nodes (down_want
            # counts un-cancellable pipeline nodes, and max_down spans
            # both tiers, so without the per-tier clamp a drained tier
            # could be driven negative)
            down_spot = jnp.where(cool <= 0.0,
                                  jnp.minimum(jnp.minimum(down_want_spot,
                                                          max_down),
                                              nodes_spot), 0.0)
            down = jnp.where(cool <= 0.0,
                             jnp.minimum(jnp.minimum(down_want,
                                                     max_down - down_spot),
                                         nodes), 0.0)
            nodes = nodes - down
            nodes_spot = nodes_spot - down_spot
            down_all = down + down_spot
            cool = jnp.where(down_all > 0.0, jnp.ceil(cool_s / dt),
                             jnp.maximum(cool - 1.0, 0.0))
            nodes_billed = nodes + nodes_spot + pipe.sum() + pipe_spot.sum() \
                + evict_bill
            spot_billed = nodes_spot + pipe_spot.sum() + evict_bill
        else:
            starting = starting.at[:, cold_ticks - 1].add(create)
            nodes_billed = jnp.asarray(static_nodes, jnp.float32)
            spot_billed = jnp.zeros(())

        # queue-delay estimator for THIS tick's arrivals: drain with the
        # capacity that will exist once in-flight creations finish, plus the
        # residual cold-start wait if capacity is still materializing.
        pending = starting.sum(axis=1)
        future_slots = (inst + pending) * ccf
        drain = jnp.maximum(future_slots / dur, 1e-6)
        # async arrivals additionally wait for the reconcile tick that
        # notices them before their instance even starts (the family's
        # cold_factor; sync creates on the arrival path, so its wait is the
        # cold start alone); a pre-warming family hides up to cold_hide
        # seconds of the cold start (the sandbox was requested that early),
        # paid for below in standing pre-warmed memory
        prewarm_hide = dec.cold_hide
        cold_full = jnp.maximum(
            fam.cold_factor * cold_ticks * dt - prewarm_hide, 0.0)
        cold_wait = jnp.where(pending > 0, cold_full,
                              jnp.where(future_slots < 0.5,
                                        jnp.maximum(2.0 * cold_ticks * dt
                                                    - prewarm_hide, 0.0),
                                        0.0))
        # a delayed arrival waits behind the backlog ahead of it — its own
        # cohort sits half in front, half behind on average
        queue_pos = jnp.maximum(queue - 0.5 * arr_delayed, 0.0)
        delay = queue_pos / drain + cold_wait

        (c_cw, c_cm, c_tw, c_tm, c_rq, c_idle, c_wfloor_node, c_mfloor) = cpu_consts
        # eviction-drained instances tear down gracefully during the notice
        # window, so they cost teardown CPU like a policy retire
        teard = ws(retire).sum() + ws(killed).sum() if has_spot \
            else ws(retire).sum()
        create_sum = ws(create).sum()
        cpu_worker = create_sum * c_cw + teard * c_tw \
            + ws(idle).sum() * c_idle * dt + c_wfloor_node * nodes_billed * dt
        cpu_master = create_sum * c_cm + teard * c_tm \
            + ws(dispatch).sum() * c_rq + c_mfloor * dt
        useful = ws(completions * dur).sum()

        # total allocated memory counts still-starting sandboxes, as the
        # oracle's per-tick sample does; the hybrid additionally holds each
        # new sandbox warm for its prewarm_s lead — a standing mass of
        # (creations/s x prewarm_s) pre-warmed instances in steady state
        prewarm_mass = ws(create * mem).sum() * prewarm_hide / dt
        # billed GB-s this tick: completions weighted by each function's
        # EXPECTED billed duration x configured GB (repro.fleet.billing) —
        # the fluid twin of the oracle's exact per-record rounding
        ys = (delay, arr, arr_delayed, ws(inst).sum(),
              ws((inst + pending) * mem).sum() + prewarm_mass,
              ws(busy_inst * mem).sum(),
              create_sum, cpu_worker, cpu_master, useful, nodes_billed,
              ws(completions).sum(), spot_billed,
              ws(completions * billed_w).sum())
        if telem:
            # in-scan telemetry (repro.obs): ys[14] is the per-tick series
            # vector (TELEM_SERIES order), ys[15] the attribution vector
            # (TELEM_ATTR order).  The eviction-storm share of this tick's
            # creation is the (capacity-scaled) recreate wave the hazard
            # triggered; everything else is ordinary churn, idle keepalive
            # is priced directly, and creation+eviction+idle subtracted
            # from cpu_worker+cpu_master leaves exactly the floors+dispatch
            # residual (master_control) — the exact-sum the attribution
            # ledger checks.
            if has_spot:
                ev_create = ws(evict_rec * scale).sum()
                ev_kill = ws(killed).sum()
            else:
                ev_create = jnp.zeros(())
                ev_kill = jnp.zeros(())
            # create-side CPU only: graceful-teardown CPU stays in the
            # master_control residual on BOTH engines (the oracle does the
            # same — see eventsim._teardown)
            cpu_creation = (create_sum - ev_create) * (c_cw + c_cm)
            cpu_evict = ev_create * (c_cw + c_cm)
            mem_pipe = ws(pending * mem).sum() + prewarm_mass
            tser = jnp.stack([
                ws(inst).sum(), ws(busy_inst).sum(), ws(queue).sum(),
                create_sum, ev_kill, ys[4], ys[5], mem_pipe, nodes_billed,
                spot_billed, cpu_worker, cpu_master])
            tattr = jnp.stack([cpu_creation, cpu_evict,
                               ws(idle).sum() * c_idle * dt, mem_pipe,
                               ev_kill, ev_create])
            ys = ys + (tser, tattr)
        return (inst, in_service, queue, starting, win_, wcur + 1,
                nodes, pipe, cool, nodes_spot, pipe_spot, spot_inst,
                evict_deficit), ys

    return step


class _Statics(NamedTuple):
    """Per-run constants of a fluid run: everything the step programs and
    the summary need besides the arrivals and the gap statistics."""
    dur: jax.Array            # (F,) mean service time, floored at dt / 4
    mem: jax.Array            # (F,) instance memory incl. overhead (MB)
    cold_ticks: int           # cold-start pipeline depth
    wbuf: int                 # policy window buffer (1 unless windowed)
    cpu_consts: tuple         # SimConfig CPU prices, in _make_step's order
    billed_w: jax.Array       # (F,) expected billed GB-s per completion
    dur_median: np.ndarray    # (F,) per-request duration lognormal
    dur_sigma: np.ndarray
    prov_ticks: int           # node provisioning pipeline depth
    edges: np.ndarray         # delay-histogram bin edges
    warm_tick: int            # first measured tick


def _run_statics(trace, policy: JaxPolicy, sim: SimConfig, dt: float, *,
                 billing, provision_s: float, nbins: int, n_ticks: int,
                 warmup_frac: float) -> _Statics:
    """The per-run constants every fluid engine entry builds from the
    trace's function profile, the policy, the simulator config and the
    horizon.
    ``billing`` is a ``repro.fleet.billing`` profile or name (default
    ``ideal``: no rounding, so a billed weight is E[duration] x configured
    GB); imported lazily, so ``repro.core`` needs no ``repro.fleet``."""
    from repro.fleet.billing import get_profile
    prof = trace.profile
    dur_mean = prof.dur_median * np.exp(prof.dur_sigma ** 2 / 2)
    wbuf = max(1, int(round(policy.window_s / dt))) \
        if get_family(policy.family).uses_window else 1
    return _Statics(
        dur=jnp.asarray(np.maximum(dur_mean, dt * 0.25), jnp.float32),
        mem=jnp.asarray(prof.memory_mb + sim.instance_overhead_mb,
                        jnp.float32),
        cold_ticks=max(1, int(round(sim.cold_start_s / dt))),
        wbuf=wbuf,
        cpu_consts=(sim.cpu_create_worker_s, sim.cpu_create_master_s,
                    sim.cpu_teardown_worker_s, sim.cpu_teardown_master_s,
                    sim.cpu_request_s, sim.cpu_idle_per_s,
                    sim.cpu_worker_floor_per_node_s,
                    sim.cpu_master_floor_per_s),
        billed_w=jnp.asarray(get_profile(
            billing if billing is not None else "ideal").billed_weights(prof),
            jnp.float32),
        dur_median=np.asarray(prof.dur_median),
        dur_sigma=np.asarray(prof.dur_sigma),
        prov_ticks=max(1, int(round(provision_s / dt))),
        edges=_delay_edges(nbins),
        warm_tick=int(n_ticks * warmup_frac))


# ---------------------------------------------------------------------------
# chunked scan: production scale without per-tick histories
# ---------------------------------------------------------------------------
#
# The scan emits nothing per tick: summary statistics (per-function
# arrival-weighted delay histograms + scalar sums) live in the scan carry,
# the time axis is segmented into fixed-size chunks, and the carry buffers
# are donated between chunk calls, so peak device memory is
# O(F * BINS + chunk * F) regardless of trace length — the 2000-function
# Fig. 9 replay, vmapped sweeps (P x F) and 100k-function planet replays
# alike.  Three step programs run the chunks (``_chunk_batch``, its
# function-sharded twin ``_chunk_batch_fnshard`` and the multi-cell
# ``repro.cells.fluid._cells_chunk_batch``); one host path around them
# builds the statics (``_run_statics``), runs the chunk loop
# (``_drive_chunks``) and solves each point's row (``_point_rows``).

# scalar per-tick series accumulated post-warmup (order matches ys[3:];
# ys[0:3] are the per-function delay / arrivals / delayed-arrivals vectors)
_ACC_NAMES = ("instances", "mem_total", "mem_busy", "creations", "cpu_worker",
              "cpu_master", "useful", "nodes", "completions", "spot_nodes",
              "billed_gb_s")


def _delay_edges(nbins: int) -> np.ndarray:
    """Log-spaced histogram bin edges over 1 ms .. ~28 h of queueing delay.
    ~1.075x per bin at nbins=256, so histogram p99s land within a few
    percent of the exact per-tick percentile."""
    return np.logspace(-3, 5, nbins - 1, dtype=np.float32)


def _delay_bin(edges, delay, nbins: int):
    """Histogram bin of each queueing delay (traced, inside a step):
    bin 0 below the first edge, the last bin above the top one."""
    return jnp.clip(jnp.searchsorted(edges, delay, side="right"), 0,
                    nbins - 1)


def _bin_reps(edges: np.ndarray) -> np.ndarray:
    """Representative delay per histogram bin: 0 below the first edge,
    geometric midpoints inside, the top edge above."""
    return np.concatenate([[0.0], np.sqrt(edges[:-1] * edges[1:]),
                           [float(edges[-1])]])


def _phi(z: np.ndarray) -> np.ndarray:
    """Standard normal CDF (Abramowitz–Stegun 7.1.26; |err| < 1.5e-7),
    vectorized — scipy is not a dependency of this repo."""
    z = np.asarray(z, np.float64)
    t = 1.0 / (1.0 + 0.3275911 * np.abs(z) / np.sqrt(2.0))
    poly = t * (0.254829592 + t * (-0.284496736 + t * (1.421413741
                + t * (-1.453152027 + t * 1.061405429))))
    erf = 1.0 - poly * np.exp(-0.5 * z * z)
    return 0.5 * (1.0 + np.sign(z) * erf)


# per-request service times are clipped lognormals (see trace.synthesize)
_DUR_FLOOR, _DUR_CAP = 0.02, 30.0

#: a function's request count is a whole number, but the cells router splits
#: arrivals into fractional per-cell shares whose float32 sums land within
#: rounding of it — on either side, depending on the device's summation
#: order.  A count this close to ``min_requests`` meets the threshold, so
#: which functions enter the geomean does not depend on the device.
_COUNT_SLACK = 1e-3


def _slowdown_geomean(hist, arrtot, edges, dur_median, dur_sigma, warm,
                      min_requests: int = 5, q: float = 0.99,
                      iid_tail: bool = True, fn_weights=None) -> float:
    """Geomean over functions of the q-quantile of per-request slowdown.

    The oracle computes p99 of (wait + service) / dur_i per REQUEST, where
    dur_i is that request's own lognormal service time — so its slowdown
    tail is driven by (long wait, short request) coincidences.  Dividing a
    single p99 delay by the MEAN duration (the naive fluid estimator)
    ignores that dispersion and can undershoot by 3-4x on bursty traces.
    Here slowdown is the mixture S = 1 + (W + warm) / D with W the
    arrival-weighted delay histogram and D an independent clipped
    lognormal:  P(S <= s) = sum_b p_b * P(D >= (w_b + warm)/(s - 1)),
    solved for the q-quantile by bisection, vectorized over functions.

    ``fn_weights`` is the super-function multiplicity (clustered traces):
    the geomean weighs each representative by its member count, and the
    finite-sample correction uses the PER-MEMBER request count (arrtot
    holds the weighted bucket total) — matching what each member would
    report unclustered.  Planet-sized histograms (>= ~4M cells) route
    through the jitted float32 bisection (`_slowdown_geomean_jax`); the
    2000-function fig9 replay and below keep the float64 numpy path
    bit-for-bit."""
    if np.asarray(hist).size >= _JAX_SOLVER_MIN_CELLS:
        with runs.span("summary.solve", on="device"):
            return _slowdown_geomean_jax(hist, arrtot, edges, dur_median,
                                         dur_sigma, warm, min_requests, q,
                                         iid_tail, fn_weights)
    with runs.span("summary.solve", on="host"):
        return _slowdown_geomean_np(hist, arrtot, edges, dur_median,
                                    dur_sigma, warm, min_requests, q,
                                    iid_tail, fn_weights)


def _slowdown_geomean_np(hist, arrtot, edges, dur_median, dur_sigma, warm,
                         min_requests, q, iid_tail, fn_weights) -> float:
    """The float64 numpy bisection behind ``_slowdown_geomean``."""
    n_eff = np.asarray(arrtot, np.float64)
    if fn_weights is not None:
        n_eff = n_eff / np.maximum(np.asarray(fn_weights, np.float64), 1e-12)
    keep = n_eff >= min_requests - _COUNT_SLACK
    if not keep.any():
        return float("nan")
    h = np.asarray(hist)[keep]
    p = h / h.sum(axis=1, keepdims=True)
    w = _bin_reps(edges)[None, :] + warm                      # (F', B)
    log_med = np.log(np.maximum(dur_median[keep], 1e-9))[:, None]
    sig = np.maximum(dur_sigma[keep], 1e-6)[:, None]
    # Finite-sample correction: the oracle reports np.percentile(q) over a
    # function's n observed requests, whose expectation is the POPULATION
    # quantile at roughly (q*(n-1)+1)/(n+1) — e.g. ~0.94 for n=20.  Solving
    # the mixture at the raw q would systematically overshoot the oracle on
    # sparsely-invoked functions, where the empirical p99 rarely reaches
    # the (long-wait, short-request) joint tail.
    # The correction assumes tail events are roughly independent across a
    # function's requests — true for sync cold starts (each arrival is
    # independently warm or cold), NOT for async backlog episodes, where
    # one burst delays a correlated block of requests and the empirical
    # percentile does reach the population tail (iid_tail=False -> raw q).
    n = n_eff[keep]
    q_eff = (q * (n - 1.0) + 1.0) / (n + 1.0) if iid_tail \
        else np.full(len(n), q)
    lo = np.full(h.shape[0], 1.0)
    hi = np.full(h.shape[0], 1.0 + w.max() / _DUR_FLOOR + 1.0)
    # The survival function runs only on occupied cells (~1% of them on
    # the Fig. 9 replay); an empty cell adds p * 0.0 = +0.0 to its row,
    # exactly what p = 0 times a finite sf added, so the dense row sums
    # below see the same values in the same order: bit for bit.
    live = np.flatnonzero(p)
    row, b = np.divmod(live, p.shape[1])
    w_live, med_live, sig_live = w[0, b], log_med[row, 0], sig[row, 0]
    sf = np.zeros(p.shape)
    sf_flat = sf.reshape(-1)
    runs.count("solve_cells", p.size)
    runs.count("solve_live_cells", live.size)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        x = w_live / np.maximum(mid - 1.0, 1e-12)[row]
        sf_flat[live] = np.where(
            x <= _DUR_FLOOR, 1.0,
            np.where(x >= _DUR_CAP, 0.0,
                     1.0 - _phi((np.log(np.maximum(x, 1e-300))
                                 - med_live) / sig_live)))
        ok = (p * sf).sum(axis=1) >= q_eff
        hi = np.where(ok, mid, hi)
        lo = np.where(ok, lo, mid)
    logs = np.log(np.maximum(0.5 * (lo + hi), 1.0))
    if fn_weights is None:
        return float(np.exp(np.mean(logs)))
    return float(np.exp(np.average(
        logs, weights=np.asarray(fn_weights, np.float64)[keep])))


#: histogram cell count at which the slowdown bisection switches from the
#: float64 numpy solver to the jitted float32 one — chosen above the
#: 2000-function fig9 replay (2000 x 256 = 512k cells stays numpy, keeping
#: checked-in baselines bitwise) and below fig9_planet (100k x 256 = 25.6M)
_JAX_SOLVER_MIN_CELLS = 1 << 22


def _phi_jax(z):
    """float32 jnp port of `_phi` (A&S 7.1.26 normal CDF)."""
    t = 1.0 / (1.0 + 0.3275911 * jnp.abs(z) / np.sqrt(2.0).astype(np.float32))
    poly = t * (0.254829592 + t * (-0.284496736 + t * (1.421413741
                + t * (-1.453152027 + t * 1.061405429))))
    erf = 1.0 - poly * jnp.exp(-0.5 * z * z)
    return 0.5 * (1.0 + jnp.sign(z) * erf)


@partial(jax.jit, static_argnames=("iters",))
def _bisect_slowdown(p, wrow, q_eff, log_med, sig, hi0, iters=60):
    lo = jnp.ones(p.shape[0], jnp.float32)
    hi = jnp.full(p.shape[0], 1.0, jnp.float32) * hi0

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        x = wrow[None, :] / jnp.maximum(mid - 1.0, 1e-12)[:, None]
        z = (jnp.log(jnp.maximum(x, 1e-30)) - log_med) / sig
        sf = jnp.where(x <= _DUR_FLOOR, 1.0,
                       jnp.where(x >= _DUR_CAP, 0.0, 1.0 - _phi_jax(z)))
        ok = (p * sf).sum(axis=1) >= q_eff
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    with jax.named_scope("slowdown_bisect"):
        lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo + hi)


def _slowdown_geomean_jax(hist, arrtot, edges, dur_median, dur_sigma, warm,
                          min_requests, q, iid_tail, fn_weights) -> float:
    """Planet-scale twin of the numpy bisection: same mixture, float32 on
    device, one fused fori_loop — 100k x 256 histograms solve in ~1 s where
    the 60-pass float64 numpy loop takes tens of seconds.  The float32
    interval bottoms out around 1e-7 relative, far below the ~7% histogram
    bin width that dominates the estimator's resolution."""
    n_eff = np.asarray(arrtot, np.float64)
    w_np = None if fn_weights is None else np.asarray(fn_weights, np.float64)
    if w_np is not None:
        n_eff = n_eff / np.maximum(w_np, 1e-12)
    keep = n_eff >= min_requests - _COUNT_SLACK
    if not keep.any():
        return float("nan")
    h = np.asarray(hist, np.float32)[keep]
    p = jnp.asarray(h) / jnp.maximum(
        jnp.asarray(h).sum(axis=1, keepdims=True), 1e-30)
    wrow = jnp.asarray(_bin_reps(edges) + warm, jnp.float32)
    log_med = jnp.asarray(
        np.log(np.maximum(np.asarray(dur_median)[keep], 1e-9)),
        jnp.float32)[:, None]
    sig = jnp.asarray(np.maximum(np.asarray(dur_sigma)[keep], 1e-6),
                      jnp.float32)[:, None]
    n = n_eff[keep]
    q_np = (q * (n - 1.0) + 1.0) / (n + 1.0) if iid_tail \
        else np.full(len(n), q)
    hi0 = np.float32(1.0 + (float(_bin_reps(edges)[-1]) + warm)
                     / _DUR_FLOOR + 1.0)
    s = np.asarray(_bisect_slowdown(p, wrow, jnp.asarray(q_np, jnp.float32),
                                    log_med, sig, hi0), np.float64)
    logs = np.log(np.maximum(s, 1.0))
    if w_np is None:
        return float(np.exp(np.mean(logs)))
    return float(np.exp(np.average(logs, weights=w_np[keep])))


def _chunk_impl(state, arr_chunk, lam0, gaps, gap_tab, dur, mem, billed_w,
                pol, fleet,
                cpu_consts, static_nodes, edges, tick0, *, warm_tick: int,
                total_ticks: int, family: str, dt: float,
                cold_ticks: int, wbuf: int, prov_ticks: int, has_fleet: bool,
                telem_slots: int = 0, weights=None):
    """Advance the simulation by one time chunk; return the carried state and
    this chunk's summary-statistic partials (host accumulates across chunks).
    Ticks at global index < warm_tick (warmup) or >= total_ticks (padding of
    the final chunk) advance state but are excluded from the statistics.

    ``telem_slots > 0`` (static) adds the bounded in-scan telemetry buffers
    (repro.obs): per-slot sums of the TELEM_SERIES vector over the WHOLE run
    (each slot covers ~total_ticks/telem_slots consecutive ticks — constant
    memory in trace length) plus the measurement-window TELEM_ATTR sums.
    With telemetry off the carry and the emitted ops are LITERALLY the
    pre-telemetry ones (the bit-for-bit guarantee the tests pin)."""
    f = arr_chunk.shape[1]
    nbins = edges.shape[0] + 1
    telem = telem_slots > 0
    step = _make_step(arr_chunk, dur, mem, billed_w, lam0, gaps, gap_tab,
                      pol, fleet,
                      cpu_consts, static_nodes, family=family, dt=dt,
                      cold_ticks=cold_ticks, wbuf=wbuf, prov_ticks=prov_ticks,
                      has_fleet=has_fleet, telem=telem, weights=weights)

    def acc_step(carry, i):
        st, hist, arrtot, sums, n = carry[:5]
        with jax.named_scope("fluid_tick"):
            st, ys = step(st, i)
        delay, arr, arr_delayed = ys[0], ys[1], ys[2]
        if weights is not None:
            # super-function multiplicity: the histogram counts REQUESTS,
            # so the representative's arrivals weigh in once per member
            arr, arr_delayed = arr * weights, arr_delayed * weights
        g = tick0 + i
        m = ((g >= warm_tick) & (g < total_ticks)).astype(jnp.float32)
        with jax.named_scope("delay_histogram"):
            b = _delay_bin(edges, delay, nbins)
            hist = hist.at[jnp.arange(f), b].add(arr_delayed * m)
            hist = hist.at[:, 0].add((arr - arr_delayed) * m)
        out = (st, hist, arrtot + arr * m,
               sums + m * jnp.stack(ys[3:3 + len(_ACC_NAMES)]), n + m)
        if telem:
            tser, tcnt, tattr = carry[5:]
            slot = jnp.clip(g * telem_slots // total_ticks, 0,
                            telem_slots - 1)
            mt = (g < total_ticks).astype(jnp.float32)   # timeline: warmup in
            out = out + (tser.at[slot].add(ys[14] * mt),
                         tcnt.at[slot].add(mt),
                         tattr + ys[15] * m)             # attribution: not
        return out, None

    init = (state, jnp.zeros((f, nbins)), jnp.zeros(f),
            jnp.zeros(len(_ACC_NAMES)), jnp.zeros(()))
    if telem:
        init = init + (jnp.zeros((telem_slots, len(TELEM_SERIES))),
                       jnp.zeros(telem_slots), jnp.zeros(len(TELEM_ATTR)))
    carry, _ = jax.lax.scan(acc_step, init, jnp.arange(arr_chunk.shape[0]))
    return carry[0], carry[1:]


def _acc_summary(hist, arrtot, sums, n, edges, dur_median, dur_sigma, warm,
                 dt, iid_tail: bool = True, fn_weights=None) -> dict:
    """Build one point's metric row from its accumulated chunk partials."""
    geo = _slowdown_geomean(hist, arrtot, edges, dur_median, dur_sigma, warm,
                            iid_tail=iid_tail, fn_weights=fn_weights)
    s = dict(zip(_ACC_NAMES, sums))
    n = max(float(n), 1e-9)
    window = n * dt
    useful = max(s["useful"], 1e-9)
    w, m = s["cpu_worker"], s["cpu_master"]
    return {
        "slowdown_geomean_p99": geo,
        "normalized_memory": float(s["mem_total"] / max(s["mem_busy"], 1e-9)),
        "creation_rate": float(s["creations"] / window),
        "cpu_overhead": float((w + m) / useful),
        "worker_share": float(w / max(w + m, 1e-9)),
        "instances_mean": float(s["instances"] / n),
        "nodes_mean": float(s["nodes"] / n),
        "node_seconds": float(s["nodes"] * dt),
        "spot_nodes_mean": float(s["spot_nodes"] / n),
        "spot_node_seconds": float(s["spot_nodes"] * dt),
        "completed": float(s["completions"]),
        "cpu_useful_s": float(s["useful"]),
        "cpu_worker_s": float(w),
        "cpu_master_s": float(m),
        "mem_total_mean": float(s["mem_total"] / n),
        "mem_busy_mean": float(s["mem_busy"] / n),
        "billed_gb_s": float(s["billed_gb_s"]),
        "ticks_measured": float(n),
    }


def _chunk_batch_impl(state, arr_chunk, lam0, gaps, gap_tab, dur, mem,
                      billed_w, pols, fleets,
                      cpu_consts, static_nodes, edges, tick0, *,
                      warm_tick: int, total_ticks: int, family: str, dt: float,
                      cold_ticks: int, wbuf: int, prov_ticks: int,
                      has_fleet: bool, telem_slots: int = 0, weights=None):
    """One time chunk for a whole batch of parameter points (vmap over the
    point axis of state/lam0/pols/fleets; ``pols`` is a STACKED params
    pytree — every leaf, scalar knob or weight array, carries a leading
    point axis).  The body runs once per trace of a step program (the
    function-sharded one calls it from its shard body), which the open
    engine run counts as ``step_traces``."""
    runs.count("step_traces", 1)

    def one(st, l0, p, fl):
        return _chunk_impl(st, arr_chunk, l0, gaps, gap_tab, dur, mem,
                           billed_w, p, fl,
                           cpu_consts,
                           static_nodes, edges, tick0, warm_tick=warm_tick,
                           total_ticks=total_ticks, family=family, dt=dt,
                           cold_ticks=cold_ticks, wbuf=wbuf,
                           prov_ticks=prov_ticks, has_fleet=has_fleet,
                           telem_slots=telem_slots, weights=weights)
    return jax.vmap(one)(state, lam0, pols, fleets)


# module-level jit so repeated simulate_chunked / sweep calls with the same
# shapes and static config hit the compile cache (a per-call jit(vmap(...))
# closure would retrace every invocation); tick0 is a traced scalar, so the
# host chunk loop reuses one executable across chunks
_chunk_batch = partial(jax.jit, static_argnames=(
    "warm_tick", "total_ticks", "family", "dt", "cold_ticks", "wbuf",
    "prov_ticks", "has_fleet", "telem_slots"),
    donate_argnums=(0,))(_chunk_batch_impl)


# ---------------------------------------------------------------------------
# device-sharded dispatch (planet scale)
# ---------------------------------------------------------------------------
#
# The function axis is embarrassingly parallel: per-function state never
# couples across functions EXCEPT through a handful of scalar reductions
# (node capacity pressure, CPU floors, the metric sums).  ``shard_map``
# splits every per-function input and carry leaf over a 1-D "functions"
# mesh, each device runs the full chunk scan on its function slice with
# per-function histograms device-local, and ONE psum per chunk restores the
# global scalar sums.  The replicated floor terms (master CPU floor, the
# static node count) are pre-divided by the device count so the psum of the
# local sums reconstructs them exactly — division by 1.0 is a bitwise
# identity and the 1-device mesh is bit-for-bit the unsharded scan (tested),
# while powers of two divide exactly.
#
# The fleet layer reduces over functions INSIDE every tick (capacity
# scaling feeds back into per-function creates), which would need a psum
# per tick, not per chunk — so fleet runs shard the POINT axis instead
# (``_chunked_summaries`` places the vmapped batch over a "points" mesh and
# lets GSPMD partition the existing ``_chunk_batch``), which also batches
# frontier candidates as grid-points x devices in one compiled dispatch.

def _largest_divisor(n: int, cap: int) -> int:
    """Largest d <= cap with n % d == 0 (device_put refuses uneven shards)."""
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def _chunk_batch_fnshard_impl(state, arr_chunk, lam0, gaps, gap_tab, dur,
                              mem, billed_w, pols, fleets, edges, tick0,
                              weights, *, mesh, cpu_consts, static_nodes,
                              warm_tick: int, total_ticks: int, family: str,
                              dt: float, cold_ticks: int, wbuf: int,
                              prov_ticks: int, telem_slots: int = 0):
    """Function-sharded twin of ``_chunk_batch_impl`` (no-fleet only; the
    caller pads F to a multiple of the mesh size with inert zero-rate
    functions).  Per-function outputs (histogram, arrival totals) stay
    device-local; the scalar sums and telemetry vectors psum once per chunk."""
    ndev = mesh.shape["functions"]
    # replicated per-tick floors: each shard carries 1/ndev of the master
    # CPU floor and the static node count so the cross-device sum of local
    # accumulators reconstructs the global ones (exact for ndev a power of
    # two; ndev=1 divides by 1.0, a bitwise identity).  The worker floor
    # multiplies the already-divided node count and needs no split.
    consts_local = cpu_consts[:-1] + (cpu_consts[-1] / ndev,)
    nodes_local = static_nodes / ndev
    telem = telem_slots > 0

    def body(st, a, l0, g, gt, du, me, bw, pl, fl, ed, t0, wt):
        st, out = _chunk_batch_impl(
            st, a, l0, g, gt, du, me, bw, pl, fl, consts_local, nodes_local,
            ed, t0, warm_tick=warm_tick, total_ticks=total_ticks,
            family=family, dt=dt, cold_ticks=cold_ticks, wbuf=wbuf,
            prov_ticks=prov_ticks, has_fleet=False, telem_slots=telem_slots,
            weights=wt)
        with jax.named_scope("chunk_psum"):
            red = (out[0], out[1], jax.lax.psum(out[2], "functions"), out[3])
            if telem:
                red = red + (jax.lax.psum(out[4], "functions"), out[5],
                             jax.lax.psum(out[6], "functions"))
        return st, red

    fP = P(None, "functions")      # leading point axis, functions sharded
    rep = P()
    st_specs = (fP, fP, fP, fP, fP, rep, rep, rep, rep, rep, rep, fP, fP)
    f1 = P("functions")
    w_spec = rep if weights is None else f1
    in_specs = (st_specs, fP, fP, f1, f1, f1, f1, f1, rep, rep, rep, rep,
                w_spec)
    out_stats = (fP, fP, rep, rep)
    if telem:
        out_stats = out_stats + (rep, rep, rep)
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=(st_specs, out_stats), check_vma=False)
    return sharded(state, arr_chunk, lam0, gaps, gap_tab, dur, mem, billed_w,
                   pols, fleets, edges, tick0, weights)


_chunk_batch_fnshard = partial(jax.jit, static_argnames=(
    "mesh", "cpu_consts", "static_nodes", "warm_tick", "total_ticks",
    "family", "dt", "cold_ticks", "wbuf", "prov_ticks", "telem_slots"),
    donate_argnums=(0,))(_chunk_batch_fnshard_impl)


def stack_params(param_trees: "list[dict]") -> dict:
    """Stack per-point params pytrees into one batched pytree: every leaf
    (scalar knob or weight array) gains a leading point axis — the batch
    axes ``_chunk_batch_impl`` vmaps over."""
    return jax.tree.map(
        lambda *leaves: np.stack([np.asarray(lf, np.float32)
                                  for lf in leaves]), *param_trees)


def _chunked_summaries(trace, policy: JaxPolicy, pols: dict,
                       fleets: np.ndarray, *, sim: SimConfig, dt: float,
                       num_nodes: float, provision_s: float, has_fleet: bool,
                       chunk_ticks: int, warmup_frac: float,
                       nbins: int, telemetry: int = 0,
                       billing=None, devices: int = 0) -> list[dict]:
    """Run a batch of policy/fleet parameter points (``pols`` a stacked
    params pytree, see ``stack_params``; ``policy`` gives the family and
    the structural knobs) through the chunked scan: one metric row each.

    ``devices > 0`` shards the dispatch over a 1-D mesh of that many local
    devices.  No-fleet runs shard the FUNCTION axis via ``shard_map`` (F
    padded to a mesh multiple with inert zero-rate functions, trimmed from
    the rows); fleet runs couple functions through per-tick capacity
    reductions, so they shard the POINT axis over the largest divisor of
    the point count within the budget (a stderr note says when that is
    fewer devices), unsharded when the batch cannot split."""
    n_points = fleets.shape[0]
    devices, telemetry = int(devices), int(telemetry)
    with runs.run(points=n_points, devices=devices) as info:
        with runs.span("prep.rate_matrix"):
            arr_np = np.asarray(rate_matrix(trace, dt))
        n_ticks, f_orig = arr_np.shape
        chunk_ticks = max(1, min(chunk_ticks, n_ticks))
        info.update(functions=f_orig, ticks=n_ticks, chunk_ticks=chunk_ticks)
        gq, alive_tab, tail_tab = gap_statistics(trace)

        with runs.span("prep.device"):
            st = _run_statics(trace, policy, sim, dt, billing=billing,
                              provision_s=provision_s, nbins=nbins,
                              n_ticks=n_ticks, warmup_frac=warmup_frac)
            weights_np = getattr(trace, "weights", None)
            lam_np = arr_np.mean(axis=0) / dt
            fn_mesh = pt_sharding = None
            if devices > 0 and not has_fleet:
                fn_mesh = device_mesh(devices, "functions")
                if f_orig % devices:
                    st, arr_np, lam_np, gq, alive_tab, tail_tab, weights_np \
                        = _pad_functions((-f_orig) % devices, st, arr_np,
                                         lam_np, gq, alive_tab, tail_tab,
                                         weights_np, trace.duration_s)
            elif devices > 0 and has_fleet:
                d = _largest_divisor(n_points, devices)
                if devices > 1 and d < devices:
                    print(f"simjax: {n_points} point(s) split over {d} of "
                          f"the {devices} requested device(s)",
                          file=sys.stderr)
                if d > 1:
                    pt_sharding = NamedSharding(device_mesh(d, "points"),
                                                P("points"))
            f = arr_np.shape[1]

            lam_eff = jnp.broadcast_to(jnp.asarray(lam_np, jnp.float32),
                                       (n_points, f))
            gaps = jnp.asarray(gq, jnp.float32)
            gap_tab = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                                   (alive_tab, tail_tab))
            edges_j = jnp.asarray(st.edges)
            weights_j = None if weights_np is None \
                else jnp.asarray(weights_np, jnp.float32)

            def init_point(fl):
                init_nodes = fl[0] if has_fleet \
                    else jnp.asarray(float(num_nodes))
                return _init_state(f, st.cold_ticks, st.wbuf, st.prov_ticks,
                                   init_nodes)

            fleets_j = jnp.asarray(fleets, jnp.float32)
            state = jax.vmap(init_point)(fleets_j)
            pols_j = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                                  pols)
            if pt_sharding is not None:
                # point-axis sharding: place the vmapped batch over the mesh
                # and let GSPMD partition the existing compiled dispatch —
                # frontier candidates run as grid-points x devices in one
                # call
                state, lam_eff, pols_j, fleets_j = jax.device_put(
                    (state, lam_eff, pols_j, fleets_j), pt_sharding)

        statics = dict(warm_tick=st.warm_tick, total_ticks=n_ticks,
                       family=policy.family, dt=dt, cold_ticks=st.cold_ticks,
                       wbuf=st.wbuf, prov_ticks=st.prov_ticks,
                       telem_slots=telemetry)

        def dispatch(state, chunks, t0):
            if fn_mesh is not None:
                return _chunk_batch_fnshard(
                    state, chunks[0], lam_eff, gaps, gap_tab, st.dur, st.mem,
                    st.billed_w, pols_j, fleets_j, edges_j, t0, weights_j,
                    mesh=fn_mesh, cpu_consts=st.cpu_consts,
                    static_nodes=float(num_nodes), **statics)
            return _chunk_batch(
                state, chunks[0], lam_eff, gaps, gap_tab, st.dur, st.mem,
                st.billed_w, pols_j, fleets_j, st.cpu_consts,
                float(num_nodes), edges_j, t0, has_fleet=has_fleet,
                weights=weights_j, **statics)

        acc = _drive_chunks(state, (arr_np,), dispatch, n_ticks=n_ticks,
                            chunk_ticks=chunk_ticks)
        rows = _point_rows(acc, st, policy.family, sim, dt, f=f_orig,
                           fn_weights=getattr(trace, "weights", None))
    if telemetry:
        for i, row in enumerate(rows):
            row["telemetry"] = assemble_telemetry(
                *(x[i] for x in acc[4:]), n_ticks, dt)
    return rows


def _pad_functions(pad, st, arr_np, lam_np, gq, alive_tab, tail_tab,
                   weights_np, duration_s):
    """Append ``pad`` inert functions to every per-function input of a
    function-sharded run, so F splits evenly over the mesh: zero arrivals
    -> zero instances, creations, memory and histogram mass (trimmed from
    the rows anyway)."""
    from repro.core.trace import KA_GRID
    n_ticks = arr_np.shape[0]
    arr_np = np.concatenate(
        [arr_np, np.zeros((n_ticks, pad), arr_np.dtype)], axis=1)
    st = st._replace(
        dur=jnp.concatenate([st.dur, jnp.ones(pad, st.dur.dtype)]),
        mem=jnp.concatenate([st.mem, jnp.zeros(pad, st.mem.dtype)]),
        billed_w=jnp.concatenate(
            [st.billed_w, jnp.zeros(pad, st.billed_w.dtype)]))
    lam_np = np.concatenate([lam_np, np.zeros(pad)])
    gq = np.concatenate([gq, np.full(pad, duration_s)])
    # never-observed-gap convention: alive = ka, tail = 1
    alive_tab = np.concatenate(
        [alive_tab, np.broadcast_to(KA_GRID, (pad, len(KA_GRID)))])
    tail_tab = np.concatenate([tail_tab, np.ones((pad, len(KA_GRID)))])
    if weights_np is not None:
        weights_np = np.concatenate([weights_np, np.zeros(pad)])
    return st, arr_np, lam_np, gq, alive_tab, tail_tab, weights_np


def _drive_chunks(state, per_tick, dispatch, *, n_ticks: int,
                  chunk_ticks: int) -> list:
    """The chunk loop of every fluid engine entry.  Each time chunk of the
    host (T, ...) arrays in ``per_tick`` goes to the device,
    ``dispatch(state, chunks, t0)`` advances the (donated) state through
    one step program and returns it with the chunk's statistics, which
    come back to the host and add into float64 sums, one per leaf, in
    chunk order."""
    sums = None
    for index, t0 in enumerate(range(0, n_ticks, chunk_ticks)):
        with runs.span("chunk", index=index):
            chunks = _upload_chunks(*(x[t0:t0 + chunk_ticks]
                                      for x in per_tick), ticks=chunk_ticks)
            with runs.span("chunk.dispatch"):
                state, out = dispatch(state, chunks,
                                      jnp.asarray(t0, jnp.int32))
            out = _read_back(out)
            with runs.span("chunk.accumulate"):
                if sums is None:
                    sums = [np.zeros(x.shape) for x in out]
                for total, x in zip(sums, out):
                    total += x
    return sums


def _point_rows(acc, st: _Statics, family: str, sim: SimConfig, dt: float,
                *, f: int, fn_weights=None) -> list[dict]:
    """One metric row per batch point from the accumulated chunk sums
    (histogram, arrival totals, metric sums, measured ticks first), the
    function axis trimmed to the first ``f`` functions."""
    hist, arrtot, sums, n = acc[:4]
    iid = get_family(family).synchronous_tail
    rows = []
    for i in range(len(n)):
        with runs.span("summary", point=i):
            rows.append(_acc_summary(
                hist[i, :f], arrtot[i, :f], sums[i], n[i], st.edges,
                st.dur_median, st.dur_sigma, sim.warm_latency_s, dt,
                iid_tail=iid, fn_weights=fn_weights))
    return rows


def _upload_chunks(*chunks, ticks: int) -> list:
    """Send one time chunk's per-tick inputs to the device, the tail chunk
    padded with zeros to ``ticks`` (the padded ticks are masked out of the
    statistics); counts their bytes as ``upload_bytes``."""
    with runs.span("chunk.upload"):
        out = []
        for c in chunks:
            if c.shape[0] < ticks:
                c = np.concatenate(
                    [c, np.zeros((ticks - c.shape[0],) + c.shape[1:],
                                 c.dtype)])
            runs.count("upload_bytes", c.nbytes)
            out.append(jnp.asarray(c))
        return out


def _read_back(out) -> list:
    """Wait for one chunk's statistics, then copy them to the host; counts
    their bytes as ``readback_bytes``."""
    with runs.span("chunk.wait"):
        jax.block_until_ready(out)
    with runs.span("chunk.readback"):
        host = [np.asarray(x) for x in out]
        runs.count("readback_bytes", sum(x.nbytes for x in host))
    return host


def simulate_chunked(trace, policy: JaxPolicy, sim: SimConfig = SimConfig(),
                     dt: float = 1.0, num_nodes: int = 8,
                     fleet: Optional[JaxFleet] = None, chunk_ticks: int = 512,
                     warmup_frac: float = 0.5, nbins: int = 256,
                     *, spec=None) -> dict:
    """Replay one trace under one policy (and optional node fleet) through
    the chunked scan and return its metric row.  Summary statistics are
    accumulated inside a segmented scan, so arbitrarily long / wide traces
    (the 2000-function Fig. 9 replay, fig9_planet's 100k functions, and
    beyond) never materialize (T, F) histories.  ``trace`` may be an
    event-level ``Trace`` or a pre-binned ``RateTrace`` (optionally
    clustered into weighted super-functions).

    ``spec`` (a ``repro.core.runspec.RunSpec``) carries the run knobs this
    engine consumes: ``devices`` for the sharded dispatch (function axis
    here; see ``_chunked_summaries``); ``telemetry=S`` (static, default
    off), S downsampled per-tick series slots plus attribution sums in the
    scan carry — constant memory — assembled into the row's ``telemetry``
    dict (repro.obs.telemetry), while ``telemetry=0`` compiles no telemetry
    op at all; and ``billing`` (a ``repro.fleet.billing`` profile or name,
    default ``ideal``), the billed-duration expectation the scan's
    ``billed_gb_s`` accumulates — the ONLY metric it touches."""
    from repro.core.runspec import RunSpec
    spec = spec if spec is not None else RunSpec()
    if not isinstance(spec, RunSpec):
        raise TypeError("simulate_chunked() spec= must be a RunSpec, got "
                        f"{type(spec).__name__}")
    has_fleet = fleet is not None
    pols = stack_params([policy.params()])
    fleets = np.asarray([fleet.params() if has_fleet
                         else np.zeros(len(_PFLEET))], np.float32)
    return _chunked_summaries(
        trace, policy, pols, fleets, sim=sim, dt=dt, num_nodes=num_nodes,
        provision_s=fleet.provision_s if has_fleet else 0.0,
        has_fleet=has_fleet, chunk_ticks=chunk_ticks,
        warmup_frac=warmup_frac, nbins=nbins, telemetry=spec.telemetry,
        billing=spec.billing, devices=spec.devices)[0]
