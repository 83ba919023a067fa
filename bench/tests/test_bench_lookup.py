"""Job entries and family references are found on disk by name: a new
entry and a new family's reference arrive as new files and drive a whole
run; a missing one fails naming the file it looked for; and the sync
reference gives, bit for bit, the rows recorded from it in
``bench/testdata/reference_rows.json`` (every point of the tiny cells at
one seed, in float32 and in the control's bfloat16), so that a change to
the shared parts or to the sync tick that moves any number shows."""

from __future__ import annotations

import copy
import dataclasses
import shutil

import pytest

from bench import gen, harness
from bench.jobs import Job
from bench.spans import NoSpans
from bench.tests.util import RATES, run_tiny, tiny_cell

FROZEN = harness.load_json(harness.ROOT / "bench" / "testdata"
                           / "reference_rows.json")


def test_new_entry_and_family_reference_arrive_as_files(tmp_path):
    """A copy of the replay entry under a new name and a copy of the sync
    reference for ``spot_aware``, a family the program registers that
    with no spot share and no hazard is the sync keepalive, under a root
    that holds nothing else."""
    new = {("entries", "replay", "replay_copy"),
           ("references", "sync", "spot_aware")}
    for kind, src, dst in new:
        assert not (harness.ROOT / "bench" / kind / f"{dst}.py").exists()
        (tmp_path / "bench" / kind).mkdir(parents=True)
        shutil.copy(harness.ROOT / "bench" / kind / f"{src}.py",
                    tmp_path / "bench" / kind / f"{dst}.py")
    base = tiny_cell("azure2k.replay")
    config = copy.deepcopy(base.config)
    config["policy"] = {"family": "spot_aware", "keepalive_s": 600.0,
                        "extra": {"spot_fraction": 0.0,
                                  "hazard_per_hour": 0.0}}
    cell = dataclasses.replace(
        base, name="spot_aware.replay_copy", config=config,
        traffic={"entry": "replay_copy", "shard": "none"}, root=tmp_path)
    rc, res, err = run_tiny(cell)
    assert rc == 0
    assert res["correct"] is True, err
    assert sorted(str(p.relative_to(tmp_path))
                  for p in tmp_path.rglob("*.py")) == [
        "bench/entries/replay_copy.py", "bench/references/spot_aware.py"]


def test_unknown_entry_names_the_missing_file():
    cell = tiny_cell("azure2k.replay")
    data = gen.generate(cell.config, 1)
    with pytest.raises(FileNotFoundError,
                       match=r"bench/entries/no_such_entry\.py"):
        Job(cell.config, {"entry": "no_such_entry", "shard": "none"}, 1,
            data, NoSpans())


def test_shard_the_entry_does_not_declare_is_refused():
    cell = tiny_cell("azure2k.sweep16")
    data = gen.generate(cell.config, 1)
    with pytest.raises(ValueError, match="functions"):
        Job(cell.config, {**cell.traffic, "shard": "functions"}, 1, data,
            NoSpans())


def test_family_without_reference_names_the_missing_file():
    """``hybrid`` is registered in the program and has no reference."""
    cell = tiny_cell("azure2k.replay")
    cell.config["policy"] = {"family": "hybrid", "keepalive_s": 600.0}
    data = gen.generate(cell.config, 1)
    with pytest.raises(FileNotFoundError,
                       match=r"bench/references/hybrid\.py"):
        harness.reference_rows(cell, data, [0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["azure2k.replay", RATES, "azure2k.sweep16"])
def test_reference_rows_are_the_recorded_ones(name, dtype):
    rec = FROZEN["cells"][name]
    cell = tiny_cell(name)
    rows = harness.reference_rows(
        cell, gen.generate(cell.config, FROZEN["seed"]), rec["points"],
        dtype=dtype)
    assert rows == rec[dtype]
