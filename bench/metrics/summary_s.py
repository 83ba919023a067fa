"""summary_s: host seconds per job in the summary solve.

The benchmark's span around ``simjax._acc_summary`` (the slowdown
bisection, on the host in float64 or on the device through
``_bisect_slowdown``), summed per traced job.
"""


def read(obs):
    jobs = obs.span_jobs
    if not jobs:
        return None
    total = sum(j["spans"].get("summary", 0.0) for j in jobs)
    return total / len(jobs) if total > 0 else None
