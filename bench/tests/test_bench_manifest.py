"""BENCHMARK.json against the files it names, and each traffic's job once
at a tiny size on the CPU."""

from __future__ import annotations

import re

import pytest

from bench import gen, harness
from bench.jobs import Job
from bench.spans import NoSpans
from bench.tests.util import (RATES, SHARDED, assert_cell_files_load,
                              assert_config_states_its_cuts, tiny_cell)

BM = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BM["configs"]] + CELLS \
        + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]] \
        + [w["traffic"] for w in BM["workloads"]] \
        + [k for c in BM["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_end_to_end_bounds_and_sources():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert {"setup_s", "fn_ticks_per_s"} <= set(e2e)
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_exist_and_load(name):
    assert_cell_files_load(name)


@pytest.mark.parametrize("conf", BM["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(conf):
    assert_config_states_its_cuts(conf)


@pytest.mark.parametrize("name", ["azure2k.replay", RATES, "azure2k.sweep16",
                                  SHARDED])
def test_traffic_job_runs_once_at_tiny_size(name):
    """Each traffic's job in process; the function-sharded replay on a
    one-device mesh of the CPU."""
    cell = tiny_cell(name)
    data = gen.generate(cell.config, 3)
    job = Job(cell.config, cell.traffic, 1, data, NoSpans())
    rows = job()
    assert len(rows) == len(job.points)
    assert all(r["slowdown_geomean_p99"] >= 1.0 for r in rows)
    assert job.work == gen.work_per_job(cell.config, len(job.points))
