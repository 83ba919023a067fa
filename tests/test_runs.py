"""Engine runs (``repro.obs.runs``): one record of host spans and counters
per call into the fluid engine.

Pinned here: every engine entry makes exactly one well-formed run record
whose spans cover the phases the benchmark reads; the byte counters equal
what the shapes give; a second call at the same shapes traces no step
program; and neither the spans nor an active profiler change a result
bit."""

import math

import jax
import numpy as np
import pytest

from repro.cells import build_cell_traces
from repro.cells.fluid import run_cells_fluid
from repro.core.eventsim import SimConfig
from repro.core.runspec import RunSpec
from repro.core.simjax import _ACC_NAMES, JaxFleet, JaxPolicy, simulate_chunked
from repro.core.trace import TraceConfig, synthesize
from repro.obs import runs, validate
from repro.opt.search import evaluate_points
from repro.scenarios import get_scenario

SIM = SimConfig()
POLICY = JaxPolicy(family="sync", keepalive_s=300.0)
# shapes no other test file compiles, so the first call here traces
F, T, CHUNK = 23, 500, 96


@pytest.fixture(scope="module")
def trace():
    return synthesize(TraceConfig(num_functions=F, duration_s=float(T),
                                  target_total_rps=5.0, seed=7))


def _one_run(call):
    """(result, the one run record the call made)."""
    before = {r.rid for r in runs.last_runs()}
    out = call()
    new = [r for r in runs.last_runs() if r.rid not in before]
    assert len(new) == 1, [r.rid for r in new]
    return out, new[0]


def _names(rec):
    return [sp.name for sp in rec.spans.spans]


def _replay(trace, **kw):
    return simulate_chunked(trace, POLICY, SIM, num_nodes=4,
                            chunk_ticks=CHUNK, **kw)


def _chunk_bytes(points, functions, nbins=256):
    """Bytes of one chunk's float32 statistics: the delay histogram,
    arrival totals, metric sums and the measured-tick count."""
    return 4 * points * (functions * nbins + functions + len(_ACC_NAMES) + 1)


def test_replay_makes_one_well_formed_run(trace):
    _, rec = _one_run(lambda: _replay(trace))
    assert validate(rec.spans) == []
    root = rec.spans.spans[0]
    assert root.name == "engine" and root.parent is None
    assert rec.args == {"run": rec.rid, "points": 1, "devices": 0,
                        "functions": F, "ticks": T, "chunk_ticks": CHUNK}
    names = _names(rec)
    chunks = math.ceil(T / CHUNK)
    for name in ("chunk", "chunk.upload", "chunk.dispatch", "chunk.wait",
                 "chunk.readback", "chunk.accumulate"):
        assert names.count(name) == chunks, name
    for name in ("prep.rate_matrix", "prep.gap_statistics",
                 "prep.gap_extract", "prep.gap_quantile", "prep.gap_tables",
                 "prep.device", "summary", "summary.solve"):
        assert names.count(name) == 1, name
    by_id = {sp.sid: sp for sp in rec.spans.spans}
    for sp in rec.spans.spans:
        if sp.name.startswith("chunk."):
            assert by_id[sp.parent].name == "chunk"
        if sp.name.startswith("prep.gap_") and sp.name != "prep.gap_statistics":
            assert by_id[sp.parent].name == "prep.gap_statistics"
    solve = next(sp for sp in rec.spans.spans if sp.name == "summary.solve")
    assert solve.args == {"on": "host"}
    assert by_id[solve.parent].name == "summary"
    # the root covers its children; its own time is what no phase names
    assert 0.0 <= runs.self_s(rec, "engine") < root.dur


def test_byte_counters_follow_the_shapes(trace):
    _, rec = _one_run(lambda: _replay(trace))
    chunks = math.ceil(T / CHUNK)
    assert rec.counters["upload_bytes"] == chunks * CHUNK * F * 4
    assert rec.counters["readback_bytes"] == chunks * _chunk_bytes(1, F)


def _rolling_windows(trace, window=256, stride=16):
    """Rolling windows the per-window ``gap_quantile`` loop evaluates:
    those ending in the measurement half (else the last one) of each
    function with more than ``window`` gaps."""
    n = 0
    for i in range(trace.num_functions):
        t = np.sort(trace.t[trace.fn == i])
        g = np.diff(t)
        if len(g) <= window:
            continue
        ends = np.arange(window, len(g) + 1,
                         max(1, min(stride, (len(g) - window) // 8 + 1)))
        n += max(1, int((t[1:][ends - 1] >= trace.duration_s / 2).sum()))
    return n


def test_gap_windows_counts_the_rolling_windows(trace):
    _, rec = _one_run(lambda: _replay(trace))
    assert rec.counters["gap_windows"] == _rolling_windows(trace) > 0


def test_solve_counts_its_cells_and_rows_match_the_dense_solve(
        trace, monkeypatch):
    """The host solve counts the histogram cells of its kept functions and
    the occupied ones it evaluates; the row is bit for bit the one the
    dense evaluation of every cell gives."""
    import repro.core.simjax as simjax
    from test_summary_solve import dense_geomean_np
    row, rec = _one_run(lambda: _replay(trace))
    cells, live = rec.counters["solve_cells"], rec.counters["solve_live_cells"]
    assert 0 < live <= cells <= F * 256 and cells % 256 == 0
    monkeypatch.setattr(simjax, "_slowdown_geomean_np", dense_geomean_np)
    assert _replay(trace) == row


def test_telemetry_leaves_are_read_back_and_counted(trace):
    _, rec = _one_run(lambda: _replay(trace, spec=RunSpec(telemetry=4)))
    from repro.obs.telemetry import TELEM_ATTR, TELEM_SERIES
    extra = 4 * (4 * len(TELEM_SERIES) + 4 + len(TELEM_ATTR))
    chunks = math.ceil(T / CHUNK)
    assert rec.counters["readback_bytes"] == \
        chunks * (_chunk_bytes(1, F) + extra)


def test_sweep_makes_one_run_with_a_summary_per_point(trace):
    pts = [{"keepalive_s": 60.0}, {"keepalive_s": 600.0},
           {"keepalive_s": 300.0, "warm_frac": 0.25}]
    _, rec = _one_run(lambda: evaluate_points(
        trace, POLICY, JaxFleet(), pts, sim=SIM, dt=1.0, warmup_frac=0.5,
        chunk_ticks=CHUNK))
    assert validate(rec.spans) == []
    assert rec.args["points"] == 3
    summaries = [sp for sp in rec.spans.spans if sp.name == "summary"]
    assert [sp.args["point"] for sp in summaries] == [0, 1, 2]
    chunks = math.ceil(T / CHUNK)
    assert rec.counters["readback_bytes"] == chunks * _chunk_bytes(3, F)
    assert rec.counters["upload_bytes"] == chunks * CHUNK * F * 4


def test_cells_engine_makes_one_well_formed_run():
    sc = get_scenario("region_failover")
    traces = build_cell_traces(sc, scale=0.05)
    sim = SimConfig(tick_s=sc.policy.tick_s)
    _, rec = _one_run(lambda: run_cells_fluid(sc, traces, sim))
    assert validate(rec.spans) == []
    c = sc.cells.cell_count
    assert rec.args["cells"] == c
    names = _names(rec)
    assert names.count("prep.gap_statistics") == c
    chunks = names.count("chunk")
    f, ticks = rec.args["functions"], rec.args["chunk_ticks"]
    assert chunks == math.ceil(rec.args["ticks"] / ticks)
    # arrivals (ticks, cells, F) int32, the floor schedule (ticks, cells)
    # float32
    assert rec.counters["upload_bytes"] == chunks * ticks * c * (f + 1) * 4
    assert rec.counters["readback_bytes"] > 0


def test_second_call_at_the_same_shapes_traces_no_step(trace):
    _, first = _one_run(lambda: _replay(trace, warmup_frac=0.4))
    _, second = _one_run(lambda: _replay(trace, warmup_frac=0.4))
    assert first.counters.get("step_traces", 0) >= 1
    assert second.counters.get("step_traces", 0) == 0


def test_function_sharded_step_counts_each_trace_once(trace):
    """The sharded program's shard body runs the batch body once per
    trace.  Its first call traces twice: the first chunk gets the state as
    ``init_point`` placed it, later chunks get it back sharded over the
    mesh, a new input sharding.  A second call traces nothing."""
    def call():
        return _replay(trace, warmup_frac=0.3, spec=RunSpec(devices=1))
    _, first = _one_run(call)
    _, second = _one_run(call)
    assert first.counters["step_traces"] == 2
    assert second.counters.get("step_traces", 0) == 0
    assert validate(first.spans) == []


def test_rows_bit_identical_under_an_active_profiler(trace, tmp_path):
    plain = _replay(trace)
    with jax.profiler.trace(str(tmp_path)):
        traced = _replay(trace)
    assert traced == plain


def test_spans_outside_a_run_only_annotate():
    before = [r.rid for r in runs.last_runs()]
    with runs.span("prep.device") as args:
        runs.count("upload_bytes", 10)
    assert args == {}
    assert [r.rid for r in runs.last_runs()] == before


def test_nested_run_joins_the_open_record():
    with runs.run(points=2) as outer:
        with runs.run(points=1) as inner:
            runs.count("upload_bytes", 5)
        outer["functions"] = 3
    rec = runs.last_runs(1)[0]
    assert inner == {"points": 1}
    assert _names(rec) == ["engine", "engine"]
    assert rec.spans.spans[1].parent == rec.spans.spans[0].sid
    assert rec.args["functions"] == 3 and rec.counters == {"upload_bytes": 5}


def test_finished_runs_are_bounded():
    for _ in range(runs.MAX_RUNS + 3):
        with runs.run():
            pass
    recs = runs.last_runs(100)
    assert len(recs) == runs.MAX_RUNS
    assert [r.rid for r in recs] == sorted(r.rid for r in recs)
    assert runs.last_runs(0) == []


def test_self_time_subtracts_children_coverage():
    from repro.obs.spans import SpanRecorder
    sp = SpanRecorder()
    root = sp.begin("chunk", "engine", 0.0, pid="engine", tid=0)
    a = sp.emit("chunk.wait", "engine", 1.0, 3.0, pid="engine", tid=0,
                parent=root)
    sp.emit("chunk.readback", "engine", 2.5, 4.0, pid="engine", tid=0,
            parent=root)
    sp.emit("inner", "engine", 1.5, 2.0, pid="engine", tid=0, parent=a)
    sp.end(root, 10.0)
    rec = runs.RunRecord(rid=-1, spans=sp, counters={})
    assert runs.self_s(rec, "chunk") == pytest.approx(10.0 - 3.0)
    assert runs.self_s(rec, "chunk.wait") == pytest.approx(1.5)
    assert runs.total_s(rec, "chunk.wait") == pytest.approx(2.0)


def test_chrome_trace_of_a_run(trace):
    _, rec = _one_run(lambda: _replay(trace))
    ev = [e for e in rec.spans.chrome_trace()["traceEvents"]
          if e["ph"] == "X"]
    assert len(ev) == len(rec.spans.spans)
    assert all(e["dur"] >= 0.0 for e in ev)
    assert np.isfinite([e["ts"] for e in ev]).all()


@pytest.mark.parametrize("devices,scopes", [
    (0, ("fluid_tick", "delay_histogram")),
    (1, ("fluid_tick", "delay_histogram", "chunk_psum"))])
def test_step_programs_carry_named_scopes(trace, monkeypatch, devices,
                                          scopes):
    """The device ops of the step programs carry stable scope names."""
    import repro.core.simjax as simjax
    name = "_chunk_batch_fnshard" if devices else "_chunk_batch"
    step, calls = getattr(simjax, name), []

    def keep(*args, **kwargs):
        calls.append((jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if isinstance(x, jax.Array) else x, args), kwargs))
        return step(*args, **kwargs)
    monkeypatch.setattr(simjax, name, keep)
    _replay(trace, spec=RunSpec(devices=devices))
    args, kwargs = calls[0]
    text = step.lower(*args, **kwargs).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope


def test_slowdown_bisection_carries_its_scope():
    import jax.numpy as jnp

    from repro.core.simjax import _bisect_slowdown
    p = jnp.full((3, 4), 0.25)
    text = _bisect_slowdown.lower(
        p, jnp.ones(4), jnp.full(3, 0.99), jnp.zeros((3, 1)),
        jnp.ones((3, 1)), jnp.float32(100.0)).as_text(debug_info=True)
    assert "slowdown_bisect" in text


def test_benchmark_hooks_are_called(trace, monkeypatch):
    """The benchmark wraps engine functions by module attribute
    (``bench.spans.WRAPPED``); each must exist and be called, through its
    module global, by a replay, a function-sharded replay or a sweep."""
    import importlib

    from bench.spans import WRAPPED
    calls = {}
    for mod_name, attr, _ in WRAPPED:
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr)

        def counted(*args, _fn=fn, _key=(mod_name, attr), **kwargs):
            calls[_key] = calls.get(_key, 0) + 1
            return _fn(*args, **kwargs)
        monkeypatch.setattr(mod, attr, counted)
    _replay(trace)
    _replay(trace, spec=RunSpec(devices=1))
    evaluate_points(trace, POLICY, JaxFleet(),
                    [{"keepalive_s": ka} for ka in (60.0, 120.0, 300.0,
                                                    600.0)],
                    sim=SIM, dt=1.0, warmup_frac=0.5, chunk_ticks=CHUNK)
    for mod_name, attr, _ in WRAPPED:
        assert calls.get((mod_name, attr), 0) >= 1, (mod_name, attr)


def _span_tree(rec):
    by_id = {sp.sid: sp.name for sp in rec.spans.spans}
    return {(sp.name, by_id.get(sp.parent)) for sp in rec.spans.spans}


def test_plain_and_cells_runs_share_one_span_tree(trace):
    """Both engines run through one host path, so a plain replay and a
    multi-region run record the same spans under the same parents."""
    _, plain = _one_run(lambda: _replay(trace))
    sc = get_scenario("region_failover")
    traces = build_cell_traces(sc, scale=0.25)
    sim = SimConfig(tick_s=sc.policy.tick_s)
    _, cells = _one_run(lambda: run_cells_fluid(sc, traces, sim))
    assert _span_tree(cells) == _span_tree(plain)
