#!/usr/bin/env python3
"""Run the fluid engine's main paths on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases (a)-(f)
    python chip_smoke.py --chips 4   # the two four-chip paths only

One chip (the default) drives the engine through the entry points a user
calls, at the registered scenarios' own size (``scale=1.0``):

  (a) ``fig9_production`` (2,000 functions, ~3.5M invocations), fluid leg;
  (b) ``fig9_planet`` (100k functions, ~50M invocations) clustered at
      1 rps;
  (c) ``region_failover``, the multi-region cells engine;
  (d) the 96-point ``DEFAULT_SPACE`` sweep of ``fleet_cost_stress``;
  (e) two Adam steps of the learned keepalive policy, ``jax.grad``
      through the scan (``fleet_cost_stress`` at 0.25, fig11's scale);
  (f) ``diurnal`` at 0.25 through both engines.

(a), (c), (d) and (e), and (b) at 0.25, run again on the host CPU, which
is the reference: the chip must agree within ``TOL`` (relative).  (f) is
held to the discrete-event oracle, pure Python and independent of the
scan, within the parity band.

``--chips 4`` runs the two multi-chip paths users rely on, each against
its unsharded run on one chip: the function-sharded planet scan and the
point-sharded fleet sweep.  Both must agree within ``SHARD_TOL`` and the
sharded state must span four devices.

Every phase prints one JSON line: the platform and kind of the devices
its result arrays lived on, wall and compile seconds of each leg, and the
largest relative chip-vs-reference gap.  Only a full pass prints the last
line, ``{"ok": true, "device": {...}}``.  Without a TPU, or without the
repository's ``src/`` next to it, the script exits non-zero and prints no
result.  It runs in one process and starts no other.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# relative chip-vs-CPU tolerances; one slowdown histogram bin is ~7% wide
TOL = {"normalized_memory": 1e-3, "creation_rate": 1e-3,
       "cpu_overhead": 1e-3, "total_cost": 1e-3,
       "slowdown_geomean_p99": 1e-2}
LOSS_TOL = 1e-3          # (e): the surrogate loss at every step
SHARD_TOL = 1e-5         # --chips 4: sharded vs unsharded, same chip type
PARITY_TOL = 0.15        # (f): the oracle-vs-fluid band the tests pin

# run sizes (the registered scenarios at their own size, except where
# noted in the module docstring)
SCALE = 1.0
REF_PLANET_SCALE = 0.25
TRAIN_SCALE = 0.25
TRAIN_STEPS = 2
DIURNAL_SCALE = 0.25

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event, secs, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


class PhaseFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# observing where the engine's arrays live
# ---------------------------------------------------------------------------

# (module, attribute) of the jitted steps each path dispatches through; the
# wrapper records the devices that hold every array they return
_WATCHED = (("repro.core.simjax", "_chunk_batch"),
            ("repro.core.simjax", "_chunk_batch_fnshard"),
            ("repro.core.simjax", "_bisect_slowdown"),
            ("repro.cells.fluid", "_cells_chunk_batch"),
            ("repro.opt.learned", "_adam_update"))


@contextlib.contextmanager
def _observe(seen: dict):
    """Record, while active, the devices of the arrays the watched steps
    return: ``seen["devices"]`` (a set) and ``seen["span"]`` (the most
    devices one array was spread over)."""
    import importlib

    import jax
    seen.setdefault("devices", set())
    seen.setdefault("span", 0)
    saved = []

    def wrap(fn):
        def watched(*args, **kwargs):
            out = fn(*args, **kwargs)
            for leaf in jax.tree.leaves(out):
                if isinstance(leaf, jax.Array):
                    devs = leaf.sharding.device_set
                    seen["devices"] |= devs
                    seen["span"] = max(seen["span"], len(devs))
            return out
        return watched

    for mod_name, attr in _WATCHED:
        mod = importlib.import_module(mod_name)
        saved.append((mod, attr, getattr(mod, attr)))
        setattr(mod, attr, wrap(getattr(mod, attr)))
    try:
        yield seen
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)


def _leg(device, fn):
    """Run ``fn`` with ``device`` as JAX's default device.  Returns its
    result and a record of the leg: wall and compile seconds and where the
    engine's arrays lived."""
    import jax
    seen: dict = {}
    c0, t0 = _compile_s[0], time.perf_counter()
    with jax.default_device(device), _observe(seen):
        out = fn()
    wall = time.perf_counter() - t0
    devs = sorted(seen["devices"], key=lambda d: d.id)
    if not devs:
        raise PhaseFailed("no engine step ran on any device")
    return out, {"platform": sorted({d.platform for d in devs}),
                 "kind": sorted({d.device_kind for d in devs}),
                 "devices": len(devs), "span": seen["span"],
                 "wall_s": wall, "compile_s": _compile_s[0] - c0}


# ---------------------------------------------------------------------------
# comparisons
# ---------------------------------------------------------------------------


def _rel(a: float, b: float) -> float:
    """|a - b| relative to the reference ``b``."""
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(b), 1e-12)


def _check_finite(rows, keys):
    for i, r in enumerate(rows):
        for k in keys:
            if not math.isfinite(float(r[k])):
                raise PhaseFailed(f"row {i}: {k} = {r[k]!r} is not finite")


def _compare(rows, ref_rows, tol: dict) -> dict:
    """Largest relative gap per key between matching rows; raises when a
    gap exceeds its tolerance."""
    if len(rows) != len(ref_rows):
        raise PhaseFailed(f"{len(rows)} rows vs {len(ref_rows)} reference "
                          f"rows")
    _check_finite(rows, tol)
    _check_finite(ref_rows, tol)
    worst = {k: 0.0 for k in tol}
    for r, ref in zip(rows, ref_rows):
        for k in tol:
            worst[k] = max(worst[k], _rel(float(r[k]), float(ref[k])))
    bad = {k: g for k, g in worst.items() if g > tol[k]}
    if bad:
        raise PhaseFailed(f"gaps over tolerance: {bad} (limits {tol}; all "
                          f"gaps {worst})")
    return worst


def _max_gap(gaps: dict) -> dict:
    key = max(gaps, key=gaps.get)
    return {"max_rel_gap": gaps[key], "max_gap_key": key, "gaps": gaps}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _scenario_rows(name, **spec):
    from repro.core.runspec import RunSpec
    from repro.scenarios.runner import run_scenario
    rows = run_scenario(name, spec=RunSpec(**spec))
    if not rows:
        raise PhaseFailed(f"{name}: no engine ran")
    return rows


def _fluid_vs_cpu(chip, cpu, name, **spec):
    spec = {"engines": ("simjax",), "billing": "ideal", **spec}
    rows, leg = _leg(chip, lambda: _scenario_rows(name, **spec))
    ref, ref_leg = _leg(cpu, lambda: _scenario_rows(name, **spec))
    return {"chip": leg, "cpu": ref_leg, **_max_gap(_compare(rows, ref, TOL))}


def phase_a(chip, cpu):
    return _fluid_vs_cpu(chip, cpu, "fig9_production", scale=SCALE)


def phase_b(chip, cpu):
    rows, leg = _leg(chip, lambda: _scenario_rows(
        "fig9_planet", engines=("simjax",), billing="ideal", scale=SCALE,
        cluster=1.0))
    _check_finite(rows, TOL)
    out = _fluid_vs_cpu(chip, cpu, "fig9_planet", scale=REF_PLANET_SCALE,
                        cluster=1.0)
    return {"full": {"chip": leg, "num_functions": rows[0]["num_functions"],
                     "invocations": rows[0]["invocations"]},
            "at_ref_scale": REF_PLANET_SCALE, **out}


def phase_c(chip, cpu):
    return _fluid_vs_cpu(chip, cpu, "region_failover", scale=SCALE)


def _sweep(devices: int = 0):
    from repro.core.runspec import RunSpec
    from repro.opt.search import evaluate_scenario
    from repro.opt.space import DEFAULT_SPACE
    return evaluate_scenario("fleet_cost_stress", DEFAULT_SPACE.points(),
                             spec=RunSpec(scale=SCALE, devices=devices))


def phase_d(chip, cpu):
    rows, leg = _leg(chip, _sweep)
    ref, ref_leg = _leg(cpu, _sweep)
    return {"points": len(rows), "sims": rows[0]["sims"], "chip": leg,
            "cpu": ref_leg, **_max_gap(_compare(rows, ref, TOL))}


def _train():
    from repro.opt.learned import train_policy
    return train_policy("fleet_cost_stress", scale=TRAIN_SCALE,
                        steps=TRAIN_STEPS).history


def phase_e(chip, cpu):
    hist, leg = _leg(chip, _train)
    ref, ref_leg = _leg(cpu, _train)
    rows = [{"loss": h} for h in hist]
    gaps = _compare(rows, [{"loss": h} for h in ref], {"loss": LOSS_TOL})
    return {"loss_chip": hist, "loss_cpu": ref, "chip": leg, "cpu": ref_leg,
            **_max_gap(gaps)}


def phase_f(chip, cpu):
    from repro.scenarios.runner import parity_report
    rows, leg = _leg(chip, lambda: _scenario_rows(
        "diurnal", engines=("eventsim", "simjax"), scale=DIURNAL_SCALE))
    gaps = parity_report(rows)
    if not gaps:
        raise PhaseFailed("diurnal: the oracle leg did not run")
    bad = {k: g for k, g in gaps.items() if not g <= PARITY_TOL}
    if bad:
        raise PhaseFailed(f"oracle parity over {PARITY_TOL}: {bad}")
    return {"chip": leg, "oracle_parity": gaps,
            "max_parity_gap": max(gaps.values())}


def _planet(devices: int):
    return _scenario_rows("fig9_planet", engines=("simjax",),
                          billing="ideal", scale=SCALE, cluster=1.0,
                          devices=devices)


def _sharded_vs_single(chip, run, chips: int):
    """``run(devices)`` sharded over ``chips`` against ``run(0)``."""
    rows, leg = _leg(chip, lambda: run(chips))
    if leg["span"] != chips:
        raise PhaseFailed(f"sharded state spans {leg['span']} device(s), "
                          f"not {chips}")
    ref, ref_leg = _leg(chip, lambda: run(0))
    tol = {k: SHARD_TOL for k in TOL}
    return {"sharded": leg, "single": ref_leg,
            **_max_gap(_compare(rows, ref, tol))}


PHASES_ONE_CHIP = {
    "a_fig9_production": phase_a,
    "b_fig9_planet_clustered": phase_b,
    "c_region_failover": phase_c,
    "d_default_space_sweep": phase_d,
    "e_learned_grad_steps": phase_e,
    "f_diurnal_oracle_parity": phase_f,
}


def phases_four_chips(chips: int):
    return {
        "fig9_planet_function_sharded":
            lambda chip, cpu: _sharded_vs_single(chip, _planet, chips),
        "default_space_point_sharded":
            lambda chip, cpu: _sharded_vs_single(chip, _sweep, chips),
    }


def run(phases: dict, chip, cpu) -> bool:
    """Run every phase; print one JSON line each.  True when all passed."""
    ok = True
    for name, fn in phases.items():
        t0 = time.perf_counter()
        try:
            rec = {"phase": name, "ok": True, **fn(chip, cpu)}
        except Exception as e:  # a failed phase must not hide the others
            ok = False
            rec = {"phase": name, "ok": False,
                   "error": f"{type(e).__name__}: {e}"}
        rec["phase_wall_s"] = time.perf_counter() - t0
        print(json.dumps(rec, default=str), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(f) on one chip (default); "
                         "4: the function- and point-sharded paths only")
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the engine ({e}); run from the "
              f"repository root", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}); "
              f"this check only runs on the chip", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cpu = jax.devices("cpu")[0]
    print(json.dumps({"devices": [str(d) for d in devs],
                      "kind": devs[0].device_kind, "compile_cache": cache}),
          flush=True)
    phases = (PHASES_ONE_CHIP if args.chips == 1
              else phases_four_chips(args.chips))
    if not run(phases, devs[0], cpu):
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
