"""Shrunk cells for the CPU tests: the cell's own files at a size a test
run holds (fewer functions at the same per-function load, a shorter
horizon, shorter chunks); a fixture deployment of the generator's
``rates`` kind with clustering (``bench/testdata/rates_clustered.json``);
and the replay with its function axis sharded over four chips.  No cell
uses the last two yet, but a later cell can name them by data alone."""

from __future__ import annotations

import copy
import dataclasses
import io
import json

from bench import harness

TINY = {"azure2k": (60, 600.0)}
#: the fixture's cell name; its configuration is already at a tiny size
RATES = "rates_clustered"
_FIXTURE = harness.ROOT / "bench" / "testdata" / f"{RATES}.json"
#: the azure2k replay, function-sharded over four chips
SHARDED = "azure2k.replay.fnshard"


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
              duration_s: float | None = None) -> harness.Cell:
    """A shrunk copy of a cell of ``BENCHMARK.json``, or one of the two
    cells above."""
    if name == RATES:
        return _rates_cell()
    if name == SHARDED:
        return dataclasses.replace(
            tiny_cell("azure2k.replay"), name=SHARDED, chips=4,
            traffic={"entry": "replay", "shard": "functions"})
    cell = copy.deepcopy(harness.load_cell(name))
    t = cell.config["trace"]
    f, dur = TINY[cell.config["name"]]
    f, dur = functions or f, duration_s or dur
    t["target_total_rps"] *= f / t["num_functions"]
    t.update(num_functions=f, duration_s=dur)
    cell.config["chunk_ticks"] = 64
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
