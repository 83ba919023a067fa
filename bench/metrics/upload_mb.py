"""upload_mb: megabytes per job the chunk loop sends to the device.

The engine's own ``upload_bytes`` counter (``repro.obs.runs``): the
arrival chunks copied host to device, the padded tail chunk included,
summed over each traced job's engine run.  A count: it repeats exactly
for a given workload.  None where the program keeps no such counter.
"""


def read(obs):
    try:
        from repro.obs.runs import last_runs
    except ImportError:
        return None
    recs = last_runs(len(obs.span_jobs))
    total = sum(r.counters.get("upload_bytes", 0) for r in recs)
    return total / 1e6 / len(recs) if total > 0 else None
