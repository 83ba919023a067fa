"""BENCHMARK.json against the files it names, and each traffic's job once
at a tiny size on the CPU."""

from __future__ import annotations

import re

import pytest

from bench import gen, harness, lookup
from bench.jobs import Job
from bench.spans import NoSpans
from bench.tests.util import RATES, SHARDED, tiny_cell

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
    """The cell's entry module exists and takes its shard, and every limit
    is a row that the cell's reference returns (run on the shrunk cell)."""
    cell = harness.load_cell(name)
    assert cell.chips in (1, 4)
    entry = lookup.load("entries", cell.traffic["entry"])
    assert cell.traffic["shard"] in entry.SHARDS
    assert isinstance(entry.ELASTIC_FLEET, bool) and callable(entry.run)
    tiny = tiny_cell(name)
    rows = harness.reference_rows(tiny, gen.generate(tiny.config, 3), [0])
    assert cell.limits and set(cell.limits) <= set(rows[0])
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("conf", BM["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(conf):
    cfg = harness.load_json(harness.ROOT / conf["file"])
    assert cfg["name"] == conf["name"]
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert key in cfg["published"]
    assert cfg["precision"] == "float32"


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
