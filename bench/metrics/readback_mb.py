"""readback_mb: megabytes per job the chunk loop reads back.

Bytes of the non-carry arrays (delay histograms, arrival totals, sums,
tick counts) that the wrapped step programs ``_chunk_batch`` and
``_chunk_batch_fnshard`` return, summed per traced job.  A count: it
repeats exactly for a given workload.
"""


def read(obs):
    jobs = obs.span_jobs
    if not jobs:
        return None
    total = sum(j["readback_bytes"] for j in jobs)
    return total / 1e6 / len(jobs) if total > 0 else None
