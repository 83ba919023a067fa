"""A deployment and its cell arrive as new files and new entries of
``BENCHMARK.json`` only.  In a copy of the benchmark, a configuration
under a new name (``azure2k`` again, family ``sync``), its replay cell,
the cell's limits and a per-layer metric of its own pass every check that
a real cell gets, at the CPU size worked out from the configuration, and
no file that was there before changes.  A cell that no per-layer metric
lists is refused, saying so; and the CPU size follows one rule for every
configuration."""

from __future__ import annotations

import copy
import io
import json
import shutil

import pytest

from bench import harness
from bench.tests.util import (TINY_CHUNK_TICKS, TINY_DURATION_S,
                              TINY_FUNCTIONS, assert_cell_files_load,
                              assert_config_states_its_cuts,
                              assert_control_fails,
                              assert_program_run_correct, cells, tiny_cell)

NEW = "azure2k_twin"
CELL = f"{NEW}.replay"
METRIC = "twin_prep_s"
BM = "BENCHMARK.json"


def _copy_benchmark(root):
    shutil.copytree(harness.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / BM, root / BM)


def _files(root) -> dict[str, bytes]:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _write_new(root, rel, text):
    path = root / rel
    assert not path.exists(), rel
    path.write_text(text)


def _add_deployment(root, trace=None, metric=True) -> dict:
    """Add the new configuration, its cell, limits and (with ``metric``) a
    per-layer metric listing the cell, as new files and appended entries;
    returns the configuration's entry."""
    base = harness.load_cell("azure2k.replay", root)
    config = copy.deepcopy(base.config)
    config.update(name=NEW, policy={"family": "sync", "keepalive_s": 600.0})
    config["trace"].update(trace or {})
    conf = {"name": NEW, "source": "https://arxiv.org/abs/2509.03104",
            "file": f"bench/configs/{NEW}.json", "reduced": [],
            "why": "a copy of azure2k under a new name"}
    bm = harness.load_json(root / BM)
    bm["configs"].append(conf)
    bm["workloads"].append({"name": CELL, "config": NEW,
                            "traffic": "replay", "chips": 1,
                            "why": "one replay of the copy"})
    _write_new(root, conf["file"], json.dumps(config, indent=1))
    _write_new(root, f"bench/limits/{CELL}.json",
               (root / "bench" / "limits" / "azure2k.replay.json")
               .read_text())
    if metric:
        bm["per_layer"].append({
            "name": METRIC, "unit": "s/job", "better": "lower",
            "source": "host_clock", "layer": "host trace preparation",
            "moves": "fn_ticks_per_s", "workloads": [CELL]})
        _write_new(root, f"bench/metrics/{METRIC}.py",
                   (root / "bench" / "metrics" / "host_prep_s.py")
                   .read_text())
    (root / BM).write_text(json.dumps(bm, indent=1))
    return conf


def _only_appended(old: dict, new: dict) -> bool:
    """Every key and entry of ``old`` is in ``new`` as it was; lists only
    grew at their end."""
    return set(old) == set(new) and all(
        new[k][:len(v)] == v if isinstance(v, list) else new[k] == v
        for k, v in old.items())


def test_new_deployment_arrives_as_files_only(tmp_path):
    _copy_benchmark(tmp_path)
    before = _files(tmp_path)
    conf = _add_deployment(tmp_path)

    assert CELL in cells(tmp_path, chips=1)
    assert_config_states_its_cuts(conf, tmp_path)
    assert_cell_files_load(CELL, tmp_path)
    cell = tiny_cell(CELL, root=tmp_path)
    assert cell.root == tmp_path
    assert (cell.config["trace"]["num_functions"],
            cell.config["trace"]["duration_s"], cell.config["chunk_ticks"]) \
        == (TINY_FUNCTIONS, TINY_DURATION_S, TINY_CHUNK_TICKS)
    assert_program_run_correct(cell)
    assert_control_fails(cell)

    out, err = io.StringIO(), io.StringIO()
    assert harness.run(["--workload", CELL, "--seed", "2147483801",
                        "--seconds", "0.5", "--trace", "1"],
                       require_chip=False, cell=cell, out=out, err=err) == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True, err.getvalue()
    assert res["metrics"][METRIC]["value"] > 0.0

    after = _files(tmp_path)
    assert sorted(set(after) - set(before)) == sorted([
        conf["file"], f"bench/limits/{CELL}.json",
        f"bench/metrics/{METRIC}.py"])
    changed = [p for p in before if p != BM and after[p] != before[p]]
    assert changed == []
    assert _only_appended(json.loads(before[BM]), json.loads(after[BM]))


def test_cell_in_no_per_layer_metric_is_refused_saying_so(tmp_path):
    _copy_benchmark(tmp_path)
    _add_deployment(tmp_path, metric=False)
    with pytest.raises(AssertionError,
                       match=f"cell '{CELL}' is listed in no per-layer "
                             "metric"):
        assert_cell_files_load(CELL, tmp_path)


def test_azure2k_shrinks_to_the_size_it_always_had():
    cell = tiny_cell("azure2k.replay")
    t = cell.config["trace"]
    assert (t["num_functions"], t["duration_s"], cell.config["chunk_ticks"]) \
        == (60, 600.0, 64)
    assert t["target_total_rps"] == 729.0 * (60 / 2000)


@pytest.mark.parametrize("functions,duration_s", [
    (40, 300.0), (40, 4800.0), (100, 300.0), (60, 600.0)])
def test_any_configuration_shrinks_by_one_rule(tmp_path, functions,
                                               duration_s):
    _copy_benchmark(tmp_path)
    rps = 729.0 * functions / 2000
    _add_deployment(tmp_path, trace={"num_functions": functions,
                                     "duration_s": duration_s,
                                     "target_total_rps": rps})
    cell = tiny_cell(CELL, root=tmp_path)
    t = cell.config["trace"]
    f = min(functions, TINY_FUNCTIONS)
    assert (t["num_functions"], t["duration_s"], cell.config["chunk_ticks"]) \
        == (f, min(duration_s, TINY_DURATION_S), TINY_CHUNK_TICKS)
    assert t["target_total_rps"] == rps * (f / functions)


def test_explicit_size_overrides_the_rule():
    t = tiny_cell("azure2k.replay", functions=30, duration_s=120.0) \
        .config["trace"]
    assert (t["num_functions"], t["duration_s"]) == (30, 120.0)
    assert t["target_total_rps"] == 729.0 * (30 / 2000)
