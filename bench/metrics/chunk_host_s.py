"""chunk_host_s: host seconds per job the chunk loop spends on its own
work.

The engine's own ``chunk`` spans less their ``chunk.wait`` children
(``repro.obs.runs``): slicing and uploading the arrival chunk,
dispatching the step, copying its outputs to the host and the float64
accumulation, summed over each traced job's engine run.  None where the
program records no such span.
"""


def read(obs):
    try:
        from repro.obs.runs import last_runs, total_s
    except ImportError:
        return None
    recs = last_runs(len(obs.span_jobs))
    if not recs or not any(sp.name == "chunk"
                           for r in recs for sp in r.spans.spans):
        return None
    return sum(total_s(r, "chunk") - total_s(r, "chunk.wait")
               for r in recs) / len(recs)
