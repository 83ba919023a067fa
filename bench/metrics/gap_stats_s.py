"""gap_stats_s: host seconds per job in the gap statistics of trace
preparation.

The engine's own ``prep.gap_statistics`` spans (``repro.obs.runs``, inside
``core/trace.gap_statistics``: gap extraction, the rolling-window
quantile and the keepalive tables), summed over each traced job's engine
run.  None where the program records no such span.
"""


def read(obs):
    try:
        from repro.obs.runs import last_runs, total_s
    except ImportError:
        return None
    recs = last_runs(len(obs.span_jobs))
    if not recs or not any(sp.name == "prep.gap_statistics"
                           for r in recs for sp in r.spans.spans):
        return None
    return sum(total_s(r, "prep.gap_statistics") for r in recs) / len(recs)
