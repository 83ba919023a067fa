"""The calls a cell's window drives: one whole job of a traffic mix.

A traffic file names its ``entry``:

* ``replay``: ``repro.core.simjax.simulate_chunked`` over the deployment's
  whole horizon, as ``runner._run_simjax`` calls it; a deployment with a
  ``cluster_below_rps`` first runs ``repro.scenarios.cluster
  .cluster_functions`` inside the job.  ``shard: functions`` shards the
  function axis over the cell's chips (``RunSpec(devices=chips)``).
* ``sweep``: ``repro.opt.search.evaluate_points`` over the traffic's
  ``points`` on the deployment's elastic fleet.

A job starts from a fresh program trace object around the benchmark's
read-only workload arrays and ends with the summary rows on the host.
"""

from __future__ import annotations

import dataclasses

from bench import gen


def program_trace(data):
    """A fresh ``repro.core.trace`` object around a workload's arrays."""
    from repro.core.trace import FunctionProfile, RateTrace, Trace
    prof = FunctionProfile(**{f.name: getattr(data.profile, f.name)
                              for f in dataclasses.fields(gen.Profile)})
    if isinstance(data, gen.RateData):
        return RateTrace(data.counts, data.tick_s, prof, data.duration_s)
    return Trace(data.t, data.fn, data.dur, prof, data.duration_s)


class Job:
    """One traffic mix bound to one deployment, its workload and chips."""

    def __init__(self, config: dict, traffic: dict, chips: int, data,
                 spans):
        from repro.core.eventsim import SimConfig
        from repro.core.simjax import JaxFleet, JaxPolicy
        self.config, self.traffic, self.data = config, traffic, data
        self.spans = spans
        self.tick = float(config["tick_s"])
        self.sim = SimConfig(tick_s=self.tick, **config["sim"])
        self.policy = JaxPolicy(**config["policy"])
        self.fleet = JaxFleet(**config["fleet"])
        self.devices = chips if traffic["shard"] == "functions" else 0
        if traffic["shard"] not in ("none", "functions"):
            raise ValueError(f"unknown shard {traffic['shard']!r}")
        self.points = traffic.get("points", [{}])
        self.entry = {"replay": self._replay,
                      "sweep": self._sweep}[traffic["entry"]]
        self.simulated_functions = None

    @property
    def work(self) -> int:
        return gen.work_per_job(self.config, len(self.points))

    def __call__(self) -> list[dict]:
        with self.spans.job():
            trace = program_trace(self.data)
            below = self.config.get("cluster_below_rps", 0.0)
            if below > 0:
                from repro.scenarios.cluster import cluster_functions
                with self.spans.span("cluster"):
                    trace = cluster_functions(
                        trace, below,
                        bins_per_octave=self.config["cluster_bins_per_octave"],
                        tick_s=self.tick)
            self.simulated_functions = trace.num_functions
            return self.entry(trace)

    def _replay(self, trace) -> list[dict]:
        from repro.core.runspec import RunSpec
        from repro.core.simjax import simulate_chunked
        c = self.config
        return [simulate_chunked(
            trace, self.policy, self.sim, dt=self.tick,
            num_nodes=c["num_nodes"], chunk_ticks=c["chunk_ticks"],
            warmup_frac=c["warmup_frac"], nbins=c["nbins"],
            spec=RunSpec(devices=self.devices))]

    def _sweep(self, trace) -> list[dict]:
        from repro.opt.search import evaluate_points
        c = self.config
        return evaluate_points(trace, self.policy, self.fleet, self.points,
                               sim=self.sim, dt=self.tick,
                               warmup_frac=c["warmup_frac"],
                               chunk_ticks=c["chunk_ticks"],
                               devices=self.devices)
