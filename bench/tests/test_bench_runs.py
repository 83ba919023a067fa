"""The engine's own spans and counters (``repro.obs.runs``) as the
benchmark reads them: one run record per job of every traffic, the
read-back count equal to the one ``bench/spans.py`` keeps, and a tiny
traced run on the CPU that reports the four readers built on them, with
the engine's spans inside the benchmark's job spans in the profiler
trace."""

from __future__ import annotations

import io
import json

import pytest

from bench import gen, harness, trace_reduce
from bench.jobs import Job
from bench.spans import NoSpans, Spans
from bench.tests.util import RATES, SHARDED, tiny_cell
from repro.obs import runs, validate

READERS = ("gap_stats_s", "chunk_wait_s", "chunk_host_s", "upload_mb")


@pytest.mark.parametrize("name", ["azure2k.replay", RATES, "azure2k.sweep16",
                                  SHARDED])
def test_each_job_makes_one_run(name):
    cell = tiny_cell(name)
    job = Job(cell.config, cell.traffic, 1, gen.generate(cell.config, 5),
              NoSpans())
    for _ in range(2):
        before = {r.rid for r in runs.last_runs()}
        job()
        new = [r for r in runs.last_runs() if r.rid not in before]
        assert len(new) == 1
        assert validate(new[0].spans) == []
        assert new[0].args["points"] == len(job.points)
    assert len(runs.last_runs()) <= runs.MAX_RUNS


@pytest.mark.parametrize("name", ["azure2k.replay", "azure2k.sweep16"])
def test_readback_count_equals_the_benchmark_wrappers(name):
    """The loop reads back every leaf the step returns, so the engine's
    count and the wrappers' count of the returned leaves agree."""
    cell = tiny_cell(name)
    spans = Spans()
    job = Job(cell.config, cell.traffic, 1, gen.generate(cell.config, 6),
              spans)
    with spans.installed():
        job()
    assert runs.last_runs(1)[0].counters["readback_bytes"] == \
        spans.jobs[-1]["readback_bytes"]


def _host_events(path):
    """(name, start, end) of every host-plane event of a profiler trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_traced_tiny_run_reports_the_engine_readers(monkeypatch):
    seen = []
    load = trace_reduce.load

    def keep_events(path):
        seen.extend(_host_events(path))
        return load(path)
    monkeypatch.setattr(trace_reduce, "load", keep_events)
    cell = tiny_cell("azure2k.replay")
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(["--workload", cell.name, "--seed", "2147483777",
                      "--seconds", "0.5", "--trace", "1"],
                     require_chip=False, cell=cell, out=out, err=err)
    assert rc == 0, err.getvalue()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(m)
    f, ticks = cell.config["trace"]["num_functions"], cell.config["chunk_ticks"]
    chunks = -(-int(cell.config["trace"]["duration_s"]) // ticks)
    assert m["upload_mb"] == chunks * ticks * f * 4 / 1e6
    assert 0.0 < m["gap_stats_s"] < m["host_prep_s"]
    assert m["chunk_wait_s"] > 0.0 and m["chunk_host_s"] > 0.0

    jobs = [(s, e) for n, s, e in seen if n == "bench.job"]
    assert len(jobs) == res["attempted"]
    for name in ("repro.engine", "repro.prep.gap_quantile",
                 "repro.chunk.wait"):
        spans = [(s, e) for n, s, e in seen if n == name]
        assert spans, name
        assert all(any(js <= s and e <= je for js, je in jobs)
                   for s, e in spans), name


def test_engine_readers_are_silent_without_engine_runs(monkeypatch):
    """What a program without the engine's runs gives the readers (as the
    parent of this change does): nothing, and no error."""
    monkeypatch.setattr(runs, "_finished", type(runs._finished)(maxlen=8))
    obs = harness.Observation(span_jobs=[{"spans": {}}], trace=None)
    for name in READERS:
        assert harness.load_reader(name)(obs) is None
