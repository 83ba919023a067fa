"""host_prep_s: host seconds per job in trace preparation.

The benchmark's spans around ``core/trace.rate_matrix``,
``gap_statistics`` and ``scenarios/cluster.cluster_functions``, summed per
traced job (nested calls count once).
"""


def read(obs):
    jobs = obs.span_jobs
    if not jobs:
        return None
    return sum(j["spans"].get("host_prep", 0.0) for j in jobs) / len(jobs)
