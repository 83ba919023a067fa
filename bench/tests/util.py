"""Shrunk cells for the CPU tests: the cell's own files at a size a test
run holds (fewer functions at the same per-function load, a shorter
horizon, shorter chunks); a fixture deployment of the generator's
``rates`` kind with clustering (``bench/testdata/rates_clustered.json``);
and the replay with its function axis sharded over four chips.  No cell
uses the last two yet, but a later cell can name them by data alone.

The checks that every cell of ``BENCHMARK.json`` gets (its files load,
the program's run is correct, the control fails the limits) live here
too, taking the cell's ``root``, so that a copy of the benchmark with a
deployment added as new files runs the same checks."""

from __future__ import annotations

import copy
import dataclasses
import io
import json
from pathlib import Path

from bench import check, gen, harness, lookup

#: the shrinking rule: at most this many functions (at the same
#: per-function load), at most this horizon, and these chunks
TINY_FUNCTIONS = 60
TINY_DURATION_S = 600.0
TINY_CHUNK_TICKS = 64
#: the fixture's cell name; its configuration is already at a tiny size
RATES = "rates_clustered"
_FIXTURE = harness.ROOT / "bench" / "testdata" / f"{RATES}.json"
#: the azure2k replay, function-sharded over four chips
SHARDED = "azure2k.replay.fnshard"


def cells(root: Path = harness.ROOT, chips: int | None = None) -> list[str]:
    """The cells of ``BENCHMARK.json`` under ``root``, those on ``chips``
    chips where it is given."""
    bm = harness.load_json(root / "BENCHMARK.json")
    return [w["name"] for w in bm["workloads"]
            if chips is None or int(w["chips"]) == chips]


def _rates_cell() -> harness.Cell:
    fx = harness.load_json(_FIXTURE)
    bm = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return harness.Cell(
        name=RATES, chips=1, config=fx["config"],
        traffic=harness.load_json(harness.ROOT / "bench" / "traffic"
                                  / "replay.json"),
        limits=fx["limits"],
        end_to_end=bm["end_to_end"],
        per_layer=bm["per_layer"])


def tiny_cell(name: str, functions: int | None = None,
              duration_s: float | None = None,
              root: Path = harness.ROOT) -> harness.Cell:
    """A shrunk copy of a cell of ``BENCHMARK.json`` under ``root``, or one
    of the two cells above.  Any configuration shrinks by the same rule:
    ``min(num_functions, TINY_FUNCTIONS)`` functions, ``min(duration_s,
    TINY_DURATION_S)`` seconds, ``TINY_CHUNK_TICKS``-tick chunks;
    ``functions`` and ``duration_s`` override the first two."""
    if name == RATES:
        return _rates_cell()
    if name == SHARDED:
        return dataclasses.replace(
            tiny_cell("azure2k.replay"), name=SHARDED, chips=4,
            traffic={"entry": "replay", "shard": "functions"})
    cell = copy.deepcopy(harness.load_cell(name, root))
    t = cell.config["trace"]
    f = functions or min(t["num_functions"], TINY_FUNCTIONS)
    dur = duration_s or min(t["duration_s"], TINY_DURATION_S)
    t["target_total_rps"] *= f / t["num_functions"]
    t.update(num_functions=f, duration_s=dur)
    cell.config["chunk_ticks"] = TINY_CHUNK_TICKS
    return cell


def run_tiny(cell: harness.Cell, seed: int = 4_294_967_311,
             seconds: float = 0.5) -> tuple[int, dict, str]:
    """Drive a whole run of a shrunk cell on the CPU (the chip check
    skipped); returns the exit code, the result line and standard
    error."""
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(["--workload", cell.name, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"],
                     require_chip=False, cell=cell, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else {}, err.getvalue()


def assert_cell_files_load(name: str, root: Path = harness.ROOT) -> None:
    """The cell's entry module exists and takes its shard, a per-layer
    metric lists it and each has its reader, and every limit is a row that
    the cell's reference returns (run on the shrunk cell)."""
    cell = harness.load_cell(name, root)
    assert cell.chips in (1, 4)
    entry = lookup.load("entries", cell.traffic["entry"], root)
    assert cell.traffic["shard"] in entry.SHARDS
    assert isinstance(entry.ELASTIC_FLEET, bool) and callable(entry.run)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer, (
        f"cell {name!r} is listed in no per-layer metric: a new cell brings "
        f"at least one per_layer entry of BENCHMARK.json whose "
        f"'workloads' name it, read by bench/metrics/<metric>.py")
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"], root))
    tiny = tiny_cell(name, root=root)
    rows = harness.reference_rows(tiny, gen.generate(tiny.config, 3), [0])
    assert cell.limits and set(cell.limits) <= set(rows[0])


def assert_config_states_its_cuts(conf: dict,
                                  root: Path = harness.ROOT) -> None:
    cfg = harness.load_json(root / conf["file"])
    assert cfg["name"] == conf["name"]
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert key in cfg["published"]
    assert cfg["precision"] == "float32"


def assert_program_run_correct(cell: harness.Cell) -> None:
    rc, res, err = run_tiny(cell)
    assert rc == 0
    assert res["correct"] is True, err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"fn_ticks_per_s", "setup_s"}
    assert err.strip().splitlines()[-1].startswith("check ")


def assert_control_fails(cell: harness.Cell) -> None:
    """The reference in bfloat16, put in the program's place, fails the
    cell's limits at each of three seeds."""
    failed = 0
    for seed in (11, 12, 13):
        data = gen.generate(cell.config, seed)
        n = len(cell.traffic.get("points", [{}]))
        pts = check.checked_points(n, cell.traffic.get("check_points", n),
                                   seed)
        ref = harness.reference_rows(cell, data, pts)
        ctl = harness.reference_rows(cell, data, pts, dtype="bfloat16")
        rows = [None] * n
        for i, r in zip(pts, ctl):
            rows[i] = r
        gaps = check.job_gaps(rows, ref, pts, n, list(cell.limits))
        failed += check.judge([gaps], cell.limits)[1]
    assert failed == 3
