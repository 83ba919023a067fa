"""chunk_wait_s: host seconds per job the chunk loop waits for the device.

The engine's own ``chunk.wait`` spans (``repro.obs.runs``: blocking on
each time chunk's step outputs, inside ``simjax._chunked_summaries``),
summed over each traced job's engine run.  None where the program records
no such span.
"""


def read(obs):
    try:
        from repro.obs.runs import last_runs, total_s
    except ImportError:
        return None
    recs = last_runs(len(obs.span_jobs))
    if not recs or not any(sp.name == "chunk.wait"
                           for r in recs for sp in r.spans.spans):
        return None
    return sum(total_s(r, "chunk.wait") for r in recs) / len(recs)
