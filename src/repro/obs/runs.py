"""Host spans and counters of each call into the fluid engine.

Every call of ``simjax._chunked_summaries`` or
``cells.fluid.cells_chunked_summaries`` opens one run (``run``): a record
of timed phases (``span``: trace preparation, each time chunk's upload,
dispatch, wait, read-back and accumulation, each point's summary solve)
and of counted quantities (``count``: bytes uploaded and read back, step
programs traced, rolling windows of the gap quantile).  Spans are kept in
a ``repro.obs.spans.SpanRecorder`` with parent ids, so ``validate`` and
``chrome_trace`` apply; times are ``time.perf_counter()`` seconds.  The
last ``MAX_RUNS`` finished runs stay readable through ``last_runs``.

Each span also enters a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, so a profiler trace (``jax.profiler.trace``, read in
Perfetto or TensorBoard) shows the host phases on the device trace's
clock.  Outside a run a span only annotates.  Spans sit at phase
granularity (about 70 per replay and 100 per 16-point sweep), never inside
per-function loops or jitted code; with the profiler off one costs about a
microsecond.

This module imports nothing of ``repro.core``, so that the trace
preparation in ``core/trace.py`` can use it.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time

import jax

from repro.obs.spans import SpanRecorder

#: finished runs kept; the bound keeps memory flat through long searches
MAX_RUNS = 8


@dataclasses.dataclass
class RunRecord:
    """One engine call: its span tree (root ``engine``) and counters."""
    rid: int
    spans: SpanRecorder
    counters: dict

    @property
    def args(self) -> dict:
        """The root span's arguments (points, functions, ticks, ...)."""
        return self.spans.spans[0].args


_finished: collections.deque = collections.deque(maxlen=MAX_RUNS)
_ids = itertools.count()
_local = threading.local()          # .record, .stack: the open run, if any


def _open_record():
    return getattr(_local, "record", None)


@contextlib.contextmanager
def span(name: str, **args):
    """Time one phase of the open run, as a child of its innermost open
    span; yields the span's argument dict (empty outside a run)."""
    with jax.profiler.TraceAnnotation(f"repro.{name}"):
        rec = _open_record()
        if rec is None:
            yield args
            return
        stack = _local.stack
        sid = rec.spans.begin(name, "engine", time.perf_counter(),
                              pid="engine", tid=0,
                              parent=stack[-1] if stack else None, **args)
        stack.append(sid)
        try:
            yield rec.spans.spans[sid].args
        finally:
            stack.pop()
            rec.spans.end(sid, time.perf_counter())


@contextlib.contextmanager
def run(**args):
    """One engine call: a new record whose root span is ``engine``; yields
    the root's argument dict, which the caller may extend.  A call made
    while a run is open joins that run as a nested ``engine`` span."""
    if _open_record() is not None:
        with span("engine", **args) as a:
            yield a
        return
    rec = RunRecord(next(_ids), SpanRecorder(), {})
    _local.record, _local.stack = rec, []
    try:
        with span("engine", run=rec.rid, **args) as a:
            yield a
    finally:
        _local.record = None
        _finished.append(rec)


def count(name: str, n: int) -> None:
    """Add ``n`` to the open run's counter ``name`` (nothing outside one)."""
    rec = _open_record()
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + int(n)


def last_runs(n: int = MAX_RUNS) -> list[RunRecord]:
    """The last ``n`` finished runs, oldest first."""
    return list(_finished)[-n:] if n > 0 else []


def total_s(record: RunRecord, name: str) -> float:
    """Summed duration of the record's spans called ``name``."""
    return sum(sp.dur for sp in record.spans.spans if sp.name == name)


def self_s(record: RunRecord, name: str) -> float:
    """Summed duration of the spans called ``name``, less the time their
    children cover."""
    spans = record.spans.spans
    total = 0.0
    for sp in spans:
        if sp.name != name:
            continue
        kids = sorted((c.t0, c.t1) for c in spans if c.parent == sp.sid)
        covered, end = 0.0, sp.t0
        for t0, t1 in kids:
            t0 = max(t0, end)
            if t1 > t0:
                covered += t1 - t0
                end = t1
        total += sp.dur - covered
    return total
