"""step_device_s: device seconds per job in the chunk step programs.

Summed device durations of the ``_chunk_batch`` and
``_chunk_batch_fnshard`` modules in the profiler trace, per device and
per traced job.
"""


def read(obs):
    if obs.trace is None:
        return None
    total = sum(v for k, v in obs.trace["module_s"].items()
                if "_chunk_batch" in k)
    return total / obs.trace["jobs"] if total > 0 else None
