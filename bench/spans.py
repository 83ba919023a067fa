"""Host spans and counters around the calls into each layer of the engine.

The benchmark records them from its own files: while ``Spans.installed``
is active, the program functions named in ``WRAPPED`` are replaced by
wrappers that open a span (a ``jax.profiler.TraceAnnotation`` named
``bench.<label>``, so that the profiler's trace holds it on the same clock
as the device, and a host-clock interval kept in memory), and the step
programs' wrappers count the bytes of the non-carry arrays they return,
which the chunk loop reads back to the host.  Nothing inside ``src/``
changes.
"""

from __future__ import annotations

import contextlib
import importlib
import time

import jax

# (module, attribute, span label); a None label counts read-back bytes
WRAPPED = (
    ("repro.core.simjax", "rate_matrix", "host_prep"),
    ("repro.core.simjax", "gap_statistics", "host_prep"),
    ("repro.core.simjax", "_chunked_summaries", "chunk_loop"),
    ("repro.opt.search", "_chunked_summaries", "chunk_loop"),
    ("repro.core.simjax", "_acc_summary", "summary"),
    ("repro.core.simjax", "_chunk_batch", None),
    ("repro.core.simjax", "_chunk_batch_fnshard", None),
)

#: labels whose time is host trace preparation (host_prep_s)
HOST_PREP = ("host_prep", "cluster")


class Spans:
    """Spans and read-back bytes, recorded per job."""

    def __init__(self):
        self.jobs: list[dict] = []      # one record per finished job
        self._stack: list[str] = []
        self._cur: dict | None = None

    @contextlib.contextmanager
    def span(self, label: str):
        """A host span; nested spans of one class count once."""
        outer = any(s in HOST_PREP for s in self._stack) \
            if label in HOST_PREP else label in self._stack
        self._stack.append(label)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench.{label}"):
                yield
        finally:
            dt = time.perf_counter() - t0
            self._stack.pop()
            if self._cur is not None and not outer:
                key = "host_prep" if label in HOST_PREP else label
                self._cur["spans"][key] = self._cur["spans"].get(key, 0.0) \
                    + dt

    @contextlib.contextmanager
    def job(self):
        """One whole job: its spans and read-back bytes form one record."""
        self._cur = {"spans": {}, "readback_bytes": 0}
        try:
            with self.span("job"):
                yield self._cur
        finally:
            self.jobs.append(self._cur)
            self._cur = None

    def _count(self, out) -> None:
        if self._cur is not None:
            self._cur["readback_bytes"] += sum(
                leaf.nbytes for leaf in jax.tree.leaves(out[1]))

    def _wrap(self, fn, label):
        if label is None:
            def counted(*args, **kwargs):
                out = fn(*args, **kwargs)
                self._count(out)
                return out
            return counted

        def spanned(*args, **kwargs):
            with self.span(label):
                return fn(*args, **kwargs)
        return spanned

    @contextlib.contextmanager
    def installed(self):
        saved = []
        for mod_name, attr, label in WRAPPED:
            mod = importlib.import_module(mod_name)
            saved.append((mod, attr, getattr(mod, attr)))
            setattr(mod, attr, self._wrap(getattr(mod, attr), label))
        try:
            yield self
        finally:
            for mod, attr, fn in reversed(saved):
                setattr(mod, attr, fn)


class NoSpans:
    """Stand-in while tracing is off: records nothing."""

    def __init__(self):
        self.jobs: list = []

    @contextlib.contextmanager
    def span(self, label: str):
        yield

    @contextlib.contextmanager
    def job(self):
        yield {}
