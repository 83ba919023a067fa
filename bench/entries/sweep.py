"""One sweep of the traffic's policy points.

``repro.opt.search.evaluate_points`` over the traffic's ``points`` on the
deployment's elastic fleet, as the frontier and evo searches call it.
``shard: points`` shards the point batch over the cell's chips
(``devices=chips``).  The reference runs the elastic fleet.
"""

SHARDS = ("none", "points")
ELASTIC_FLEET = True


def run(job, trace) -> list[dict]:
    from repro.opt.search import evaluate_points
    c = job.config
    return evaluate_points(trace, job.policy, job.fleet, job.points,
                           sim=job.sim, dt=job.tick,
                           warmup_frac=c["warmup_frac"],
                           chunk_ticks=c["chunk_ticks"],
                           devices=job.devices)
