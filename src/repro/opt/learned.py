"""Gradient-learned autoscaling policies through the differentiable scan.

The policy-as-pytree redesign makes the policy itself the optimization
variable: a family's ``learnable`` axes (e.g. the learned family's MLP
weight pytree ``theta``) ride the chunked ``lax.scan`` as traced leaves, so
``jax.grad`` of a scalar objective w.r.t. those leaves differentiates
through every simulated tick — the ROADMAP's "learned policies" item.

The objective is a SMOOTH SURROGATE of the frontier axes, not the frontier
metrics themselves: the reported p99 slowdown runs through a histogram
scatter-add and a host-side bisection (zero/undefined gradients), so
training minimizes

    loss = cost_per_million_proxy + w_lat * slowdown_proxy

where the cost proxy bills the scan's node-seconds, master-CPU, billed
GB-s, idle-memory and completion sums through a ``repro.fleet.billing``
profile (bitwise the old node+master repricing under ``ideal``; provider
profiles add the per-request / per-GB-s / warm-pool terms so training
optimizes the SAME dollars the frontier ranks on), and the slowdown proxy
replaces the
per-function p99 with a differentiable tail estimate: per function,
1 + (mean wait + delay-weighted mean wait + warm hop) / mean duration,
geometric-averaged with arrival weights.  The delay-weighted mean
(sum w*d^2 / sum w*d) up-weights exactly the long-delay mass that drives
the p99, without sorting.

Trained policies are CLAIMS until the oracle confirms them: ``confirm``
replays the trained configuration through the discrete-event oracle and
judges the standard parity band, reusing the same spot-check/demotion
contract the frontier engine applies to swept winners.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.eventsim import SimConfig
from repro.core.policies import init_theta
from repro.core.policy_api import get_family
from repro.core.simjax import (_PFLEET, JaxPolicy, _init_state, _make_step,
                               _run_statics)
from repro.core.runspec import RunSpec
from repro.core.trace import Trace, gap_statistics, rate_matrix
from repro.fleet.billing import (BillingProfile, apply_throttle,
                                 resolve_profile)
from repro.fleet.nodes import NodeType
from repro.opt.search import default_fleet, evaluate_scenario
from repro.scenarios.registry import get_scenario
from repro.scenarios.runner import parity_report, run_scenario
from repro.scenarios.spec import Scenario


def make_loss(trace: Trace, policy: JaxPolicy, sim: SimConfig = SimConfig(),
              dt: float = 1.0, num_nodes: int = 8, fleet=None,
              warmup_frac: float = 0.5, w_lat: float = 4.0,
              trunc_ticks: int = 64, node_type: NodeType = NodeType(),
              billing: Union[str, BillingProfile, None] = None):
    """Build ``(loss_fn, params0)``: a jit-able scalar objective over the
    policy's params PYTREE, differentiable w.r.t. every leaf (a learned
    family's weights, but equally a sync policy's ``keepalive_s`` — the
    gradient-correctness test differentiates exactly that).

    The loss runs the same segmented scan shape as ``simulate_chunked``,
    with one addition: the carried state is ``stop_gradient``-ed at chunk
    boundaries (truncated backprop-through-time, window ``trunc_ticks``).
    Full-horizon BPTT through this recurrence amplifies the adjoint by a
    few percent per tick — by ~100 ticks the float32 cotangents overflow to
    NaN — while the policy's causal influence on cost/latency is
    concentrated well inside a minute; truncation keeps the gradient both
    finite and informative.  Per-tick statistics are accumulated as sums
    inside the scan (no (T, F) histories), so training scales like the
    chunked simulator."""
    arr_np = rate_matrix(trace, dt)
    n_ticks, f = arr_np.shape
    trunc = max(1, min(int(trunc_ticks), n_ticks))
    n_chunks = -(-n_ticks // trunc)
    pad = n_chunks * trunc - n_ticks
    arr = jnp.asarray(np.concatenate(
        [arr_np, np.zeros((pad, f), arr_np.dtype)]))
    has_fleet = fleet is not None
    # per-TIER node rates: the spot discount applies only to the scan's
    # spot node-seconds (ys[12]), exactly as repro.fleet.costs bills —
    # discounting the whole fleet would overstate any partial-spot savings
    prof = resolve_profile(billing)
    statics = _run_statics(trace, policy, sim, dt, billing=prof,
                           provision_s=fleet.provision_s if has_fleet
                           else 0.0, nbins=256, n_ticks=n_ticks,
                           warmup_frac=warmup_frac)
    lam0 = jnp.asarray(arr_np.mean(axis=0) / dt, jnp.float32)
    gq, alive_tab, tail_tab = gap_statistics(trace)
    gaps = jnp.asarray(gq, jnp.float32)
    gap_tab = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                           (alive_tab, tail_tab))
    fl = jnp.asarray(fleet.params() if has_fleet else np.zeros(len(_PFLEET)),
                     jnp.float32)
    # padded ticks advance state but carry zero weight, like _chunk_impl
    ticks = np.arange(n_chunks * trunc)
    mask = jnp.asarray(((ticks >= statics.warm_tick) & (ticks < n_ticks))
                       .astype(np.float32))
    od_rate = node_type.price_per_hour
    spot_rate = od_rate * (1.0 - prof.spot_discount)
    family = policy.family

    def loss_fn(params) -> jnp.ndarray:
        step = _make_step(arr, statics.dur, statics.mem, statics.billed_w,
                          lam0, gaps, gap_tab, params, fl,
                          statics.cpu_consts, float(num_nodes),
                          family=family, dt=dt,
                          cold_ticks=statics.cold_ticks, wbuf=statics.wbuf,
                          prov_ticks=statics.prov_ticks, has_fleet=has_fleet)

        def tick(carry, t):
            st, a_tot, d1, d2, scalars = carry
            st, ys = step(st, t)
            delay, arr_t, arr_delayed = ys[0], ys[1], ys[2]
            m = mask[t]
            w = arr_delayed * m
            scalars = scalars + m * jnp.stack(
                [ys[10], ys[8], ys[11], ys[12], ys[13], ys[4] - ys[5]])
            # ^ nodes, cpu_master, completed, spot nodes, billed GB-s,
            #   idle (warm-pool) MB
            return (st, a_tot + arr_t * m, d1 + w * delay,
                    d2 + w * delay * delay, scalars), None

        def chunk(carry, c):
            st, *acc = carry
            st = jax.tree.map(jax.lax.stop_gradient, st)   # truncated BPTT
            (st, *acc), _ = jax.lax.scan(
                tick, (st, *acc), c * trunc + jnp.arange(trunc))
            return (st, *acc), None

        init_nodes = fl[0] if has_fleet else jnp.asarray(float(num_nodes))
        init = (_init_state(f, statics.cold_ticks, statics.wbuf,
                            statics.prov_ticks, init_nodes),
                jnp.zeros(f), jnp.zeros(f), jnp.zeros(f), jnp.zeros(6))
        (_, a_tot, d1, d2, scalars), _ = jax.lax.scan(
            chunk, init, jnp.arange(n_chunks))

        # $-cost proxy billed through the profile: node-seconds per tier
        # (weighted — serverless profiles zero the node axis), master CPU,
        # plus the provider terms (per-request fee, billed GB-s via
        # ys[13]'s analytic expectation, warm-pool GB-s from the idle
        # memory sum).  Under ``ideal`` every added term is x*0 and the
        # weight is 1.0, so the proxy is bitwise the old node+master math.
        node_seconds, master_s = scalars[0] * dt, scalars[1]
        spot_seconds = jnp.minimum(scalars[3] * dt, node_seconds)
        completed = jnp.maximum(scalars[2], 1.0)
        warm_gb_s = jnp.maximum(scalars[5], 0.0) * dt / 1024.0
        cost = (((node_seconds - spot_seconds) / 3600.0 * od_rate
                 + spot_seconds / 3600.0 * spot_rate)
                * prof.node_hour_weight
                + master_s / 3600.0 * prof.master_vcpu_per_hour
                + prof.per_request * completed
                + prof.per_gb_s * scalars[4]
                + prof.warm_gb_s_rate * warm_gb_s)
        cost_per_million = cost / completed * 1e6
        # slowdown proxy: mean wait + delay-weighted mean wait per function
        mean_wait = d1 / jnp.maximum(a_tot, 1e-9)
        tail_wait = d2 / jnp.maximum(d1, 1e-9)
        slow = 1.0 + (mean_wait + tail_wait + sim.warm_latency_s) \
            / statics.dur
        wf = a_tot / (a_tot + 1.0)          # smooth min-request weighting
        slow_geo = jnp.exp((wf * jnp.log(slow)).sum()
                           / jnp.maximum(wf.sum(), 1e-9))
        return cost_per_million + w_lat * slow_geo

    return loss_fn, policy.params()


def _adam_update(g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    def upd(m_, v_):
        mh = m_ / (1 - b1 ** t)
        vh = v_ / (1 - b2 ** t)
        return lr * mh / (jnp.sqrt(vh) + eps)
    return jax.tree.map(upd, m, v), m, v


@dataclasses.dataclass
class TrainResult:
    policy: JaxPolicy            # the trained configuration
    scenario: str
    scale: float
    history: list                # loss per step
    wall_s: float

    def summary(self) -> dict:
        return {"scenario": self.scenario, "scale": self.scale,
                "steps": len(self.history) - 1,
                "loss_initial": self.history[0], "loss_final": self.history[-1],
                "wall_s": round(self.wall_s, 3)}


def train_policy(scenario: Union[str, Scenario], family: str = "learned",
                 scale: float = 0.25, steps: int = 80, lr: float = 0.05,
                 seed: int = 0, w_lat: float = 4.0,
                 sim: Optional[SimConfig] = None,
                 log: Optional[Callable[[str], None]] = None,
                 telemetry=None) -> TrainResult:
    """Train a policy family's learnable leaves on one scenario's workload
    by Adam over ``jax.grad`` of the surrogate loss, through the scan.

    Only the axes the family declares ``learnable`` move; sweepable scalar
    knobs stay at the spec's values (they belong to the frontier grid).
    ``telemetry`` (a ``repro.obs.RunTelemetry``) receives the full
    training-loss series, one ``train_step`` event per gradient step.
    """
    sc = get_scenario(scenario) if isinstance(scenario, str) else scenario
    say = log or (lambda s: None)
    tel = telemetry.emit if telemetry is not None else (lambda *a, **k: None)
    fam = get_family(family)
    learnable = set(fam.learnable_axes())
    if not learnable:
        raise ValueError(f"policy family {family!r} declares no learnable "
                         f"axes; registered learnable families train here")
    sim = sim or SimConfig(tick_s=sc.policy.tick_s)
    # the learned family's weight pytree gets its deterministic init here;
    # other families' learnable axes start from the spec/extra values
    spec = dataclasses.replace(sc.policy, kind=family,
                               theta=init_theta(seed)
                               if "theta" in learnable else sc.policy.theta)
    policy = spec.to_jax()
    # train on the workload as the scenario's provider actually runs it
    # (cpu-throttled durations; identity under ``ideal``)
    trace = apply_throttle(sc.build_trace(scale), sc.billing)
    fleet = default_fleet(sc)
    loss_fn, params0 = make_loss(trace, policy, sim=sim, dt=sim.tick_s,
                                 fleet=fleet, w_lat=w_lat,
                                 billing=sc.billing)

    frozen = {k: v for k, v in params0.items() if k not in learnable}
    theta = {k: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), v)
             for k, v in params0.items() if k in learnable}

    @jax.jit
    def value_and_grad(th):
        return jax.value_and_grad(lambda t: loss_fn({**frozen, **t}))(th)

    t0 = time.time()
    m = jax.tree.map(jnp.zeros_like, theta)
    v = jax.tree.map(jnp.zeros_like, theta)
    history: list = []
    best, best_theta = float("inf"), theta
    for t in range(1, steps + 1):
        val, g = value_and_grad(theta)      # loss AT the current theta
        history.append(float(val))
        tel("train_step", scenario=sc.name, step=t, loss=float(val))
        if float(val) < best:
            best, best_theta = float(val), theta
        delta, m, v = _adam_update(g, m, v, t, lr)
        theta = jax.tree.map(lambda p, d: p - d, theta, delta)
        if t % max(1, steps // 5) == 0:
            say(f"train[{sc.name}] step {t}/{steps}: loss {float(val):.4f}")
    val, _ = value_and_grad(theta)          # the final point joins the race
    history.append(float(val))
    if float(val) < best:
        best, best_theta = float(val), theta
    # write EVERY trained leaf back into the spec, whatever its axis name:
    # spec fields (theta, keepalive_s, ...) are replaced directly, novel
    # axes land in the ``extra`` mapping — a family is never silently
    # returned untrained because its learnable axis isn't called "theta"
    vals = {k: jax.tree.map(np.asarray, v) for k, v in best_theta.items()}
    spec_fields = {f.name for f in dataclasses.fields(spec)}
    spec_map = {"cc": "container_concurrency"}
    rep, extra_new = {}, dict(spec.extra or {})
    for k, v in vals.items():
        fk = spec_map.get(k, k)
        if fk in spec_fields:
            rep[fk] = v
        else:
            extra_new[k] = v
    trained = dataclasses.replace(spec, extra=extra_new or None, **rep)
    return TrainResult(policy=trained.to_jax(), scenario=sc.name, scale=scale,
                       history=history, wall_s=time.time() - t0)


def refine_leaves(scenario: Union[str, Scenario], point: dict,
                  axes: Sequence[str], scale: float = 0.25, steps: int = 6,
                  lr: float = 0.08, w_lat: float = 4.0,
                  sim: Optional[SimConfig] = None,
                  billing: Union[str, BillingProfile, None] = None) -> dict:
    """Gradient-refine the named CONTINUOUS policy axes of one searched
    point on one scenario: a few Adam steps over ``jax.grad`` of the same
    surrogate loss ``train_policy`` minimizes, differentiating the scalar
    leaves (keepalive_s, target, prewarm_s, ...) instead of a weight
    pytree — the local-polish move the evo engine applies to elite
    individuals, reaching configurations BETWEEN any grid's rungs.

    Returns a new point dict: ``point`` with each refined axis replaced by
    its best-loss value, clipped into the family's declared AxisSpec
    bounds (so a refined elite is always re-evaluable).  Axes the pinned
    policy's params pytree does not carry are skipped; with nothing to
    refine the point is returned unchanged."""
    from repro.opt.search import point_scenario
    sc = get_scenario(scenario) if isinstance(scenario, str) else scenario
    sc_pin = point_scenario(sc, point)
    fam = get_family(sc_pin.policy.kind)
    sim = sim or SimConfig(tick_s=sc_pin.policy.tick_s)
    prof = resolve_profile(billing, sc.billing)
    policy = sc_pin.policy.to_jax()
    fleet = default_fleet(sc_pin)
    fleet = dataclasses.replace(fleet, **{k: float(v)
                                          for k, v in point.items()
                                          if k in _PFLEET})
    trace = apply_throttle(sc_pin.build_trace(scale), prof)
    loss_fn, params0 = make_loss(trace, policy, sim=sim, dt=sim.tick_s,
                                 fleet=fleet, w_lat=w_lat, billing=prof)
    live = [a for a in axes
            if a in params0 and np.ndim(params0[a]) == 0
            and a in fam.axis_names()]
    if not live:
        return dict(point)
    frozen = {k: v for k, v in params0.items() if k not in live}
    theta = {k: jnp.asarray(params0[k], jnp.float32) for k in live}

    @jax.jit
    def value_and_grad(th):
        return jax.value_and_grad(lambda t: loss_fn({**frozen, **t}))(th)

    m = jax.tree.map(jnp.zeros_like, theta)
    v = jax.tree.map(jnp.zeros_like, theta)
    best, best_theta = float("inf"), theta

    def clip(th):
        # a gradient step must not leave the declared envelope: clip each
        # leaf into its AxisSpec bounds after every update
        return {k: jnp.clip(t, fam.axis(k).lo, fam.axis(k).hi)
                for k, t in th.items()}

    for t in range(1, steps + 1):
        val, g = value_and_grad(theta)
        if float(val) < best:
            best, best_theta = float(val), theta
        # relative step: the leaves live on wildly different scales
        # (keepalive in seconds vs target in [0, 4]), so Adam's unit step
        # is rescaled by each leaf's magnitude
        delta, m, v = _adam_update(g, m, v, t, lr)
        theta = clip({k: theta[k] - delta[k] * jnp.maximum(
            jnp.abs(theta[k]), 1.0) for k in theta})
    val, _ = value_and_grad(theta)
    if float(val) < best:
        best, best_theta = float(val), theta
    return {**point, **{k: float(np.clip(float(v_), fam.axis(k).lo,
                                         fam.axis(k).hi))
                        for k, v_ in best_theta.items()}}


def learned_scenario(sc: Scenario, result: TrainResult) -> Scenario:
    """The scenario re-specced to run the trained policy (both engines)."""
    pol = result.policy
    spec = dataclasses.replace(
        sc.policy, kind=pol.family, keepalive_s=pol.keepalive_s,
        window_s=pol.window_s, target=pol.target,
        container_concurrency=pol.cc, prewarm_s=pol.prewarm_s,
        theta=pol.theta, extra=pol.extra)
    return dataclasses.replace(sc, policy=spec)


def evaluate_trained(scenario: Union[str, Scenario], result: TrainResult,
                     scale: float = 1.0,
                     billing: Union[str, BillingProfile, None] = None) -> dict:
    """One frontier-style metric row (cost, p99, memory, ...) for the
    trained policy at the given scale — comparable against swept rows
    billed on the same basis."""
    sc = get_scenario(scenario) if isinstance(scenario, str) else scenario
    return evaluate_scenario(learned_scenario(sc, result), [{}],
                             spec=RunSpec(scale=scale, billing=billing))[0]


def confirm(scenario: Union[str, Scenario], result: TrainResult,
            scale: float = 0.25, tol: float = 0.15) -> dict:
    """Oracle spot-check of the trained policy: replay the learned
    configuration through BOTH engines and judge the parity band — the
    same trust gate swept frontier winners pass before being shipped."""
    sc = get_scenario(scenario) if isinstance(scenario, str) else scenario
    rows = run_scenario(learned_scenario(sc, result),
                        spec=RunSpec(scale=scale, force_oracle=True))
    gaps = parity_report(rows)
    ok = bool(gaps) and all(g <= tol for g in gaps.values())
    return {"scenario": sc.name, "scale": scale, "gaps": gaps, "pass": ok}
