"""The benchmark's frozen workload generator.

A copy of the profile draws and the two synthesizers of
``repro.core.trace`` as they stood when the benchmark was defined, so that
a later change to the program's generator cannot move the yardstick.  It
returns plain arrays; ``bench.jobs`` wraps them in the program's trace
types for each job.

* ``events``: a flat invocation stream (arrival time, function id, pure
  execution seconds), sorted by time: doubly-stochastic arrivals (thinned
  Poisson under a sinusoidal intensity) and clipped lognormal durations.
* ``rates``: per-tick Poisson arrival counts under the same intensity, as
  a (ticks, functions) int32 matrix.

Both draw the per-function profile first: log-uniform rates rescaled to
the target aggregate load, lognormal median durations, memory from the
Azure quantiles and a burst phase.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    num_functions: int
    duration_s: float
    seed: int
    target_total_rps: float
    min_rate: float
    max_rate: float
    dur_median_s: float
    dur_sigma: float
    dur_cap_s: float
    burst_period_s: float
    burst_amp: float


@dataclasses.dataclass(frozen=True)
class Profile:
    rate: np.ndarray          # (F,) mean requests/s
    dur_median: np.ndarray    # (F,) seconds
    dur_sigma: np.ndarray     # (F,)
    memory_mb: np.ndarray     # (F,)
    phase: np.ndarray         # (F,) burst phase offset


@dataclasses.dataclass(frozen=True)
class EventData:
    t: np.ndarray             # (N,) arrival seconds, sorted
    fn: np.ndarray            # (N,) int32 function ids
    dur: np.ndarray           # (N,) pure execution seconds
    profile: Profile
    duration_s: float


@dataclasses.dataclass(frozen=True)
class RateData:
    counts: np.ndarray        # (T, F) int32 arrivals per tick
    tick_s: float
    profile: Profile
    duration_s: float


def make_profile(cfg: TraceConfig) -> Profile:
    rng = np.random.default_rng(cfg.seed)
    f = cfg.num_functions
    rate = np.exp(rng.uniform(np.log(cfg.min_rate), np.log(cfg.max_rate), f))
    rate *= cfg.target_total_rps / rate.sum()
    dur_median = np.clip(
        np.exp(rng.normal(np.log(cfg.dur_median_s), 0.8, f)), 0.05,
        cfg.dur_cap_s)
    dur_sigma = np.full(f, cfg.dur_sigma)
    memory_mb = rng.choice([128, 128, 128, 256, 256, 512],
                           size=f).astype(np.float64)
    phase = rng.uniform(0, 2 * np.pi, f)
    return Profile(rate, dur_median, dur_sigma, memory_mb, phase)


def synthesize_events(cfg: TraceConfig) -> EventData:
    rng = np.random.default_rng(cfg.seed + 1)
    prof = make_profile(cfg)
    ts, fns, durs = [], [], []
    for i in range(cfg.num_functions):
        lam_max = prof.rate[i] * (1 + cfg.burst_amp)
        n = rng.poisson(lam_max * cfg.duration_s)
        if n == 0:
            continue
        t = np.sort(rng.uniform(0, cfg.duration_s, n))
        intensity = (1 + cfg.burst_amp * np.sin(
            2 * np.pi * t / cfg.burst_period_s + prof.phase[i])) \
            / (1 + cfg.burst_amp)
        keep = rng.uniform(size=n) < intensity
        t = t[keep]
        if len(t) == 0:
            continue
        d = np.clip(rng.lognormal(np.log(prof.dur_median[i]),
                                  prof.dur_sigma[i], len(t)),
                    0.02, cfg.dur_cap_s)
        ts.append(t)
        fns.append(np.full(len(t), i, np.int32))
        durs.append(d)
    t = np.concatenate(ts)
    order = np.argsort(t, kind="stable")
    return EventData(t[order], np.concatenate(fns)[order],
                     np.concatenate(durs)[order], prof, float(cfg.duration_s))


def synthesize_rates(cfg: TraceConfig, tick_s: float) -> RateData:
    rng = np.random.default_rng(cfg.seed + 1)
    prof = make_profile(cfg)
    f = cfg.num_functions
    t_ticks = int(np.ceil(cfg.duration_s / tick_s))
    counts = np.empty((t_ticks, f), np.int32)
    block = max(1, int(4_000_000 // max(f, 1)))
    for b0 in range(0, t_ticks, block):
        b1 = min(b0 + block, t_ticks)
        t_mid = (np.arange(b0, b1, dtype=np.float64) + 0.5) * tick_s
        mod = 1.0 + cfg.burst_amp * np.sin(
            2 * np.pi * t_mid[:, None] / cfg.burst_period_s
            + prof.phase[None, :])
        lam = np.clip(prof.rate[None, :] * mod, 0.0, None) * tick_s
        counts[b0:b1] = rng.poisson(lam).astype(np.int32)
    return RateData(counts, float(tick_s), prof, float(cfg.duration_s))


def trace_config(trace: dict, seed: int) -> TraceConfig:
    """The generator's settings from a configuration file's ``trace``
    group and a run's seed (any whole number; folded into NumPy's
    non-negative seed range)."""
    fields = {f.name for f in dataclasses.fields(TraceConfig)} - {"seed"}
    return TraceConfig(seed=int(seed) % (1 << 63),
                       **{k: trace[k] for k in sorted(fields)})


def generate(config: dict, seed: int):
    """Build a configuration's workload for one seed: ``EventData`` or
    ``RateData`` as its ``trace.kind`` says."""
    cfg = trace_config(config["trace"], seed)
    kind = config["trace"]["kind"]
    if kind == "events":
        data = synthesize_events(cfg)
    elif kind == "rates":
        data = synthesize_rates(cfg, config["tick_s"])
    else:
        raise ValueError(f"unknown trace kind {kind!r}")
    freeze(data)
    return data


def freeze(data) -> None:
    """Make every array of a workload read-only, so that no job can change
    the input the next job and the reference read."""
    for obj in (data, data.profile):
        for f in dataclasses.fields(obj):
            v = getattr(obj, f.name)
            if isinstance(v, np.ndarray):
                v.flags.writeable = False


def simulated_ticks(config: dict) -> int:
    return int(np.ceil(config["trace"]["duration_s"] / config["tick_s"]))


def work_per_job(config: dict, points: int) -> int:
    """Requested function-ticks of one job: policy points x the
    deployment's function count x simulated ticks (not what the program
    simulates after clustering)."""
    return points * config["trace"]["num_functions"] * simulated_ticks(config)
