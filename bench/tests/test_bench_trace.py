"""The profiler-trace reduction: on hand-made events, and on a small trace
recorded on a TPU v5e chip (``bench/testdata``)."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parents[1] / "testdata"


def _table(events):
    return trace_reduce._table(events)


def test_merged_union_of_intervals():
    s = np.array([5.0, 0.0, 2.0, 10.0])
    e = np.array([6.0, 3.0, 4.0, 12.0])
    ms, me = trace_reduce._merged(s, e)
    assert ms.tolist() == [0.0, 5.0, 10.0]
    assert me.tolist() == [4.0, 6.0, 12.0]


def test_reduce_hand_made_trace():
    ops = [("fusion.1", 100, 50), ("all-reduce.3", 160, 20),
           ("fusion.1", 170, 10), ("fusion.3", 250, 10),
           ("fusion.2", 400, 100)]
    mods = [("jit__chunk_batch_impl", 100, 90), ("jit_other", 400, 100)]
    tr = {"devices": {"/device:TPU:0": {"ops": _table(ops),
                                         "modules": _table(mods)}},
          "spans": [("bench.job", 0, 300), ("bench.summary", 200, 290),
                    ("bench.job", 350, 600), ("bench.host_prep", 360, 390)]}
    red = trace_reduce.reduce(tr)
    ns = 1e-9
    assert red["window_s"] == pytest.approx(600 * ns)
    assert red["busy_s"] == pytest.approx(180 * ns)
    assert red["idle_share"] == pytest.approx(1 - 180 / 600)
    assert red["collective_s"] == pytest.approx(20 * ns)
    assert red["module_s"]["jit__chunk_batch_impl"] == pytest.approx(90 * ns)
    assert red["top_ops"][0] == ["fusion.2", pytest.approx(100 * ns)]
    assert [(lbl, round(s / ns)) for lbl, s in red["idle_gaps"]] == [
        ("between_jobs", 140), ("job", 100), ("job", 100),
        ("summary", 70), ("job", 10)]
    assert red["jobs"] == 2


def test_reduce_without_job_span_is_none():
    tr = {"devices": {}, "spans": [("bench.summary", 0, 10)]}
    assert trace_reduce.reduce(tr) is None


CHIP = sorted(DATA.glob("*.xplane.pb.gz"))


@pytest.mark.parametrize("path", CHIP, ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    """A trace of two jobs of a shrunk azure2k replay (60 functions, 300
    ticks), recorded on a TPU v5 lite; the expected numbers were read
    from it when it was recorded."""
    expected = json.loads(
        path.with_name(path.name.replace(".xplane.pb.gz", ".json"))
        .read_text())
    red = trace_reduce.reduce(trace_reduce.load(str(path)))
    for k in ("window_s", "busy_s", "idle_share", "collective_s"):
        assert red[k] == pytest.approx(expected[k], rel=1e-9, abs=1e-12)
    assert red["jobs"] == expected["jobs"]
    assert 0.0 < red["busy_s"] < red["window_s"]
    step = sum(v for k, v in red["module_s"].items() if "_chunk_batch" in k)
    assert step == pytest.approx(expected["step_s"], rel=1e-9)
    # a module's span also covers the short gaps between its ops
    assert 0.0 < step <= red["window_s"]
    # the busy time equals a brute-force union of the device's op intervals
    tr = trace_reduce.load(str(path))
    w0 = min(s for n, s, _ in tr["spans"] if n == trace_reduce.JOB_SPAN)
    w1 = max(e for n, _, e in tr["spans"] if n == trace_reduce.JOB_SPAN)
    ops = next(iter(tr["devices"].values()))["ops"]
    edges = np.unique(np.clip(np.r_[ops["start"], ops["start"] + ops["dur"],
                                    w0, w1], w0, w1))
    mid = 0.5 * (edges[:-1] + edges[1:])
    covered = np.zeros(len(mid), bool)
    for s, d in zip(ops["start"], ops["dur"]):
        covered |= (mid >= s) & (mid < s + d)
    assert red["busy_s"] == pytest.approx(
        float(np.diff(edges)[covered].sum()) * 1e-9, rel=1e-6)
