"""One replay of the deployment's whole horizon.

``repro.core.simjax.simulate_chunked`` over the job's trace, as
``runner._run_simjax`` calls it.  ``shard: functions`` shards the
function axis over the cell's chips (``RunSpec(devices=chips)``).  The
reference replays the deployment's static cluster.
"""

SHARDS = ("none", "functions")
ELASTIC_FLEET = False


def run(job, trace) -> list[dict]:
    from repro.core.runspec import RunSpec
    from repro.core.simjax import simulate_chunked
    c = job.config
    return [simulate_chunked(
        trace, job.policy, job.sim, dt=job.tick,
        num_nodes=c["num_nodes"], chunk_ticks=c["chunk_ticks"],
        warmup_frac=c["warmup_frac"], nbins=c["nbins"],
        spec=RunSpec(devices=job.devices))]
