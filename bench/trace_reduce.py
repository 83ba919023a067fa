"""From a profiler trace to the benchmark's device numbers.

``load`` reads a ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain arrays: for each device plane its op events, its asynchronous op
events and its module events (name, start, duration in ns), and the
host spans the benchmark wrote as ``TraceAnnotation``s (names starting with ``bench.``), all on the
profiler's one clock.  ``reduce`` turns that into:

* ``window_s``: from the start of the first traced job span to the end of
  the last;
* ``busy_s``: the union of the device's op intervals inside the window,
  averaged over the devices that ran anything;
* ``idle_share``: 1 - busy / window, averaged the same way;
* ``module_s``: summed device time of each program module, per device;
* ``collective_s``: summed device time of the cross-device collectives
  (synchronous or asynchronous ops), per device;
* ``top_ops``: the ten op names that took most device time, per device;
* ``idle_gaps``: the ten longest gaps between busy intervals on the first
  device, each labelled with the innermost benchmark span the host was in
  at the gap's middle (``between_jobs`` outside every job).
"""

from __future__ import annotations

import re

import numpy as np

SPAN_PREFIX = "bench."
JOB_SPAN = "bench.job"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|psum", re.IGNORECASE)


def _line_kind(name: str) -> str | None:
    low = name.lower()
    if low == "xla ops":
        return "ops"
    if low == "xla modules":
        return "modules"
    if low == "async xla ops":
        return "async"
    return None


def load(path: str) -> dict:
    """Device op and module events and the host spans of one trace (a
    ``.xplane.pb``, or one compressed as ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices: dict = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SYSTEM" not in plane.name.upper():
            rec = devices.setdefault(plane.name,
                                     {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                kind = _line_kind(line.name)
                if kind is None:
                    continue
                rec[kind].extend((e.name, e.start_ns, e.duration_ns)
                                 for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": {k: {kind: _table(v) for kind, v in rec.items()}
                        for k, rec in devices.items()},
            "spans": spans}


def _short(name: str) -> str:
    """An op event is named by its HLO text; keep the instruction name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _table(events) -> dict:
    return {"name": [_short(e[0]) for e in events],
            "start": np.asarray([e[1] for e in events], np.float64),
            "dur": np.asarray([e[2] for e in events], np.float64)}


def _merged(start, end):
    """Union of intervals as sorted disjoint (start, end) arrays."""
    if len(start) == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    run_end = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > run_end[:-1]]
    starts = s[new]
    ends = np.r_[run_end[np.flatnonzero(new)[1:] - 1], run_end[-1]]
    return starts, ends


def _label(spans, t: float) -> str:
    """The innermost benchmark span covering time t."""
    best, best_len = "between_jobs", np.inf
    for name, s, e in spans:
        if s <= t <= e and e - s < best_len:
            best, best_len = name[len(SPAN_PREFIX):], e - s
    return best


def reduce(tr: dict, top: int = 10) -> dict | None:
    """The device numbers of one trace; None when it holds no job span or
    no device ran anything in its window."""
    jobs = [(s, e) for name, s, e in tr["spans"] if name == JOB_SPAN]
    if not jobs:
        return None
    w0, w1 = min(s for s, _ in jobs), max(e for _, e in jobs)
    window = w1 - w0
    busy, idle, module, collective = [], [], {}, []
    op_time: dict = {}
    first = None
    for dev in sorted(tr["devices"]):
        rec = tr["devices"][dev]
        ops = rec["ops"] if len(rec["ops"]["start"]) else rec["modules"]
        s = np.clip(ops["start"], w0, w1)
        e = np.clip(ops["start"] + ops["dur"], w0, w1)
        ms, me = _merged(s, e)
        b = float((me - ms).sum())
        if b <= 0.0:
            continue
        busy.append(b)
        idle.append(1.0 - b / window)
        if first is None:
            first = (ms, me)
        inside = (ops["start"] >= w0) & (ops["start"] < w1)
        coll = 0.0
        for name, d, ok in zip(ops["name"], ops["dur"], inside):
            if ok:
                op_time[name] = op_time.get(name, 0.0) + d
                if COLLECTIVE.search(name):
                    coll += d
        asy = rec.get("async")
        if asy is not None:
            coll += sum(d for name, st, d in zip(asy["name"], asy["start"],
                                                 asy["dur"])
                        if w0 <= st < w1 and COLLECTIVE.search(name))
        collective.append(coll)
        mods = rec["modules"]
        for name, st, d in zip(mods["name"], mods["start"], mods["dur"]):
            if w0 <= st < w1:
                module[name] = module.get(name, 0.0) + d
    if not busy:
        return None
    ndev = len(busy)
    ms, me = first
    gs = np.r_[w0, me]
    ge = np.r_[ms, w1]
    gaps = [(float(a), float(b)) for a, b in zip(gs, ge) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return {
        "window_s": window * ns,
        "busy_s": float(np.mean(busy)) * ns,
        "idle_share": float(np.mean(idle)),
        "devices": ndev,
        "module_s": {k: v * ns / ndev for k, v in module.items()},
        "collective_s": float(np.mean(collective)) * ns,
        "top_ops": [[k, v * ns / ndev] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(tr["spans"], 0.5 * (a + b)), (b - a) * ns]
                      for a, b in gaps[:top]],
        "jobs": len(jobs),
    }
