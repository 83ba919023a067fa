"""The calls a cell's window drives: one whole job of a traffic mix.

A traffic file names its ``entry``, a module ``bench/entries/<entry>.py``
(found by ``bench.lookup``).  The module declares the ``SHARDS`` it
accepts, whether its reference runs on the deployment's elastic fleet
(``ELASTIC_FLEET``), and ``run(job, trace)``, which runs one job on the
program's trace and returns the summary rows.  A shard other than
``none`` gives the job the cell's chips (``Job.devices``).  A deployment
with a ``cluster_below_rps`` first runs ``repro.scenarios.cluster
.cluster_functions`` inside the job, whatever the entry.

A job starts from a fresh program trace object around the benchmark's
read-only workload arrays and ends with the summary rows on the host.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

from bench import gen, lookup


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
                 spans, root: Path = lookup.ROOT):
        from repro.core.eventsim import SimConfig
        from repro.core.simjax import JaxFleet, JaxPolicy
        self.config, self.traffic, self.data = config, traffic, data
        self.spans = spans
        self.tick = float(config["tick_s"])
        self.sim = SimConfig(tick_s=self.tick, **config["sim"])
        self.policy = JaxPolicy(**config["policy"])
        self.fleet = JaxFleet(**config["fleet"])
        self.entry = lookup.load("entries", traffic["entry"], root)
        if traffic["shard"] not in self.entry.SHARDS:
            raise ValueError(f"entry {traffic['entry']!r} takes a shard in "
                             f"{self.entry.SHARDS}, not {traffic['shard']!r}")
        self.devices = 0 if traffic["shard"] == "none" else chips
        self.points = traffic.get("points", [{}])
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
            return self.entry.run(self, trace)
