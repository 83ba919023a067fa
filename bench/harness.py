"""The benchmark harness, driven by ``BENCHMARK.json``.

A run of one cell (``bench/run.py --workload <cell> ...``):

1. refuses to run without a TPU, or with fewer chips than the cell asks;
2. turns on JAX's persistent compilation cache;
3. builds the cell's workload from ``--seed`` with the frozen generator
   (``bench.gen``);
4. runs one whole job, which compiles every shape the window uses;

that is the set-up (``setup_s``, from process start).  The window then
runs whole jobs back to back until ``--seconds`` have passed and ends at
the end of the last one.  With ``--trace 1`` the profiler records the
first jobs of the window (at most ``TRACE_JOBS``) with the benchmark's
host spans, and the run reports the cell's per-layer metrics instead of
the end-to-end ones.  After the window the device's peak memory is read,
and the plain reference of the configuration's policy family recomputes
the checked rows from the same workload; ``bench.check`` compares every
window job's rows with it.

Everything that belongs to one configuration, traffic mix, job entry,
policy family, per-layer metric or cell is found by name:
``bench/configs/<config>.json`` (the ``file`` of the configuration),
``bench/traffic/<traffic>.json``, ``bench/entries/<entry>.py`` (the
traffic's ``entry``: the call the window drives, ``bench.jobs``),
``bench/references/<family>.py`` (the configuration's ``policy.family``:
its tick and summary, on the shared parts of ``bench.reference``),
``bench/metrics/<metric>.py`` and ``bench/limits/<cell>.json``.  Modules
are looked up under the cell's ``root`` (``bench.lookup``), so a new cell
brings its parts as new files.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

from bench import check, gen, lookup, reference, trace_reduce
from bench.jobs import Job
from bench.spans import NoSpans, Spans

ROOT = lookup.ROOT
TRACE_JOBS = 2
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: Path = ROOT


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(root / "bench" / "traffic"
                                  / f"{w['traffic']}.json"),
                limits=limits["limits"],
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
                root=root)


def load_reader(metric: str, root: Path = ROOT):
    return lookup.load("metrics", metric, root).read


class CompileLog:
    """Compile seconds and backend compilations, from JAX's monitoring."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs
        if event == _BACKEND_COMPILE:
            self.compiles += 1


@dataclasses.dataclass
class Observation:
    """What the per-layer readers read."""
    span_jobs: list
    trace: dict | None


def _finite(x):
    return x if math.isfinite(x) else 1e308


def reference_rows(cell: Cell, data, points: list[int],
                   dtype: str = "float32"):
    """The reference's rows of the checked ``points``: the tick and summary
    of the configuration's policy family, on the elastic fleet where the
    traffic's entry declares it; ``dtype="bfloat16"`` gives the control."""
    family = lookup.load("references", cell.config["policy"]["family"],
                         cell.root)
    fleet = lookup.load("entries", cell.traffic["entry"],
                        cell.root).ELASTIC_FLEET
    wl = reference.prepare(cell.config, data)
    pts = [{**cell.config["policy"], **cell.traffic.get("points", [{}])[i]}
           for i in points]
    return family.simulate(wl, cell.config, pts, fleet=fleet, dtype=dtype)


def run(argv=None, *, t0: float | None = None, require_chip: bool = True,
        cell: Cell | None = None, out=None, err=None) -> int:
    """One run of one cell; returns the exit code.  ``require_chip`` and
    ``cell`` are for tests, which drive a shrunk cell on the CPU."""
    t0 = time.perf_counter() if t0 is None else t0
    out = out or sys.stdout
    err = err or sys.stderr
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell or load_cell(args.workload)

    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=err)
        return 1
    used = devs[:cell.chips]
    if require_chip:
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    else:
        cache_dir = None
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)

    data = gen.generate(cell.config, args.seed)
    spans = Spans() if args.trace else NoSpans()
    job = Job(cell.config, cell.traffic, cell.chips, data, spans,
              cell.root)
    n_points = len(job.points)
    points = check.checked_points(n_points,
                                  cell.traffic.get("check_points", n_points),
                                  args.seed)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    installed = spans.installed() if args.trace else contextlib.nullcontext()
    results, walls = [], []
    with installed:
        job()                               # compiles every shape
        spans.jobs.clear()
        compiles_setup, compile_s_setup = log.compiles, log.seconds
        setup_s = time.perf_counter() - t0
        if args.trace:
            # host spans come from the benchmark's annotations; the Python
            # tracer would record every call of the host layers and slow
            # them several-fold
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        w0 = time.perf_counter()
        try:
            while True:
                tj = time.perf_counter()
                results.append(job())
                walls.append(time.perf_counter() - tj)
                window_s = time.perf_counter() - w0
                if window_s >= args.seconds or (
                        args.trace and len(results) >= TRACE_JOBS):
                    break
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        compiles_window = log.compiles - compiles_setup
    stats = [d.memory_stats() or {} for d in used]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    simulated = job.simulated_functions
    work = job.work
    del job
    gc.collect()

    ref = reference_rows(cell, data, points)
    keys = list(cell.limits)
    gaps = [check.job_gaps(rows, ref, points, n_points, keys)
            for rows in results]
    checks, failed = check.judge(gaps, cell.limits)

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    result = {"correct": failed == 0 and len(results) > 0,
              "attempted": len(results), "failed": failed}
    print(json.dumps({"cell": cell.name, "seed": args.seed,
                      "compile_cache": cache_dir,
                      "setup_compile_s": compile_s_setup,
                      "setup_compiles": compiles_setup,
                      "in_window_compiles": compiles_window,
                      "job_walls_s": walls, "window_s": window_s,
                      "work_per_job": work,
                      "simulated_functions": simulated,
                      "peak_bytes_in_use": [int(s.get("peak_bytes_in_use", 0))
                                            for s in stats],
                      "checked_points": points}), file=out, flush=True)
    if args.trace:
        found = list(Path(trace_dir).rglob("*.xplane.pb"))
        red = trace_reduce.reduce(trace_reduce.load(str(found[0]))) \
            if found else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs = Observation(span_jobs=spans.jobs, trace=red)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"], cell.root)(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["top_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        print(json.dumps({"trace": red, "span_jobs": spans.jobs}),
              file=out, flush=True)
    else:
        values = {"fn_ticks_per_s": work * len(results) / window_s,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": _finite(v["value"]),
                            "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['value'] <= v['limit'] else 'FAIL'}", file=err)
    print(json.dumps(result), file=out, flush=True)
    return 0
