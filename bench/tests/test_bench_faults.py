"""A whole run with the timed path broken underneath comes out not
correct: a step that returns its state unchanged, half of the batch left
out with the sums extrapolated from the rest, an answer altered where it
is produced, and (the function-sharded replay over four virtual CPU
devices, in a child process) the exchange between chips left out."""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import repro.core.simjax as simjax
import repro.opt.search as search
from bench import harness
from bench.tests.util import RATES, SHARDED, run_tiny, tiny_cell


def _state_unchanged(monkeypatch):
    orig = simjax._chunk_batch

    def step(state, *args, **kwargs):
        kept = jax.tree.map(jnp.copy, state)
        _, out = orig(state, *args, **kwargs)
        return kept, out
    monkeypatch.setattr(simjax, "_chunk_batch", step)


def _half_functions(monkeypatch):
    orig = simjax._chunk_batch

    def step(state, arr, *args, **kwargs):
        half = arr.shape[1] // 2
        st, out = orig(state, arr.at[:, half:].set(0), *args, **kwargs)
        return st, (out[0], out[1], out[2] * 2.0) + tuple(out[3:])
    monkeypatch.setattr(simjax, "_chunk_batch", step)


def _half_points(monkeypatch):
    orig = search._chunked_summaries

    def summaries(trace, policy, pols, fleets, **kwargs):
        half = max(1, fleets.shape[0] // 2)
        rows = orig(trace, policy, jax.tree.map(lambda x: x[:half], pols),
                    fleets[:half], **kwargs)
        return (rows * 2)[:fleets.shape[0]]
    monkeypatch.setattr(search, "_chunked_summaries", summaries)


def _answer_altered(monkeypatch):
    orig = simjax._acc_summary

    def summary(*args, **kwargs):
        row = orig(*args, **kwargs)
        return {**row, "creation_rate": row["creation_rate"] * 1.01}
    monkeypatch.setattr(simjax, "_acc_summary", summary)


FAULTS = {
    "azure2k.replay": (_state_unchanged, _half_functions, _answer_altered),
    RATES: (_state_unchanged, _half_functions, _answer_altered),
    "azure2k.sweep16": (_state_unchanged, _half_points, _answer_altered),
}


@pytest.mark.parametrize("name,fault", [
    (n, f) for n, fs in FAULTS.items() for f in fs],
    ids=lambda x: x if isinstance(x, str) else x.__name__.strip("_"))
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    rc, res, err = run_tiny(tiny_cell(name))
    assert rc == 0
    assert res["correct"] is False, err


CHILD = """
import jax
from bench.tests.util import SHARDED, run_tiny, tiny_cell
cell = tiny_cell(SHARDED)
rc, sound, _ = run_tiny(cell)
jax.lax.psum = lambda x, axis_name, **kw: x      # the exchange left out
jax.clear_caches()
rc2, broken, _ = run_tiny(cell)
print(rc, sound["correct"], rc2, broken["correct"])
"""


def test_function_sharded_replay_without_exchange_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT),
                                           str(harness.ROOT / "src")]))
    res = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=harness.ROOT)
    assert res.returncode == 0, res.stderr[-4000:]
    assert res.stdout.split()[-4:] == ["0", "True", "0", "False"], \
        res.stdout + res.stderr[-4000:]
